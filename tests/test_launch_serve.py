"""The serving launcher as a user runs it: its exit code, and its
one-replica-per-device placement.  Each case runs ``python -m
repro.launch.serve`` (or a short script) in a child process on the CPU, so
the process exit code itself is what is checked, and virtual devices stay
out of this process."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SMOKE = ["--arch", "qwen2.5-3b", "--smoke", "--requests", "4",
         "--new-tokens", "4"]


def _run(argv: list[str], tmp_path, devices: int = 1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, env=env, timeout=300)


@pytest.mark.parametrize("faults,ok", [(None, True),
                                       ("engine.decode:raise", False)])
def test_serve_exit_code_reports_failed_requests(tmp_path, faults, ok):
    argv = ["-m", "repro.launch.serve", *SMOKE]
    if faults:
        argv += ["--inject-faults", faults]
    proc = _run(argv, tmp_path)
    assert (proc.returncode == 0) == ok, proc.stderr[-3000:]
    assert "power: not measured" in proc.stdout
    if not ok:
        assert "FAILED requests" in proc.stderr


def test_replicas_each_on_own_device(tmp_path):
    """Four replicas on four (virtual) devices: each replica's params and
    KV state are committed to its own device, and greedy outputs equal a
    one-replica fleet's, mixed and disaggregated alike."""
    code = textwrap.dedent("""
        import json, jax
        from repro.launch import serve
        base = ["--arch", "qwen2.5-3b", "--smoke", "--requests", "6",
                "--new-tokens", "5", "--prompt-len", "40"]
        model = serve.init_model("qwen2.5-3b", smoke=True)
        out = {}
        for name, extra in (("one", []), ("mixed", ["--replicas", "4"]),
                            ("disagg", ["--replicas", "4", "--replica-roles",
                                        "prefill,decode,decode,decode"])):
            args = serve.build_parser().parse_args(base + extra)
            fleet = serve.build(args, model)
            reqs = serve.make_requests(args, fleet.cfg)
            stats = fleet.serve(reqs)
            devs = [sorted({d.id for x in jax.tree_util.tree_leaves(
                        (e.params, e._state)) for d in x.devices()})
                    for e in fleet.engines]
            out[name] = dict(tokens=[r.output for r in reqs], devices=devs,
                             migrations=stats.kv_migrations)
        print(json.dumps(out))
    """)
    proc = _run(["-c", code], tmp_path, devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["one"]["devices"] == [[0]]
    for name in ("mixed", "disagg"):
        assert out[name]["devices"] == [[0], [1], [2], [3]]
        assert out[name]["tokens"] == out["one"]["tokens"]
    assert out["disagg"]["migrations"] > 0
