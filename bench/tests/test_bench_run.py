"""A run that finds no TPU fails and reports no number."""
import json

from bench import run


def test_no_tpu_no_result(capsys):
    rc = run.main(["--workload", "qwen2.5-3b.reasoning", "--seed", "2147483659",
                   "--seconds", "51", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in out.err
    for line in out.out.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        assert not (isinstance(obj, dict) and "metrics" in obj)


def test_unknown_workload_fails():
    try:
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    except SystemExit as e:
        assert e.code != 0
    else:
        raise AssertionError("an unknown workload ran")
