"""Multi-device paths (MoE EP, LSE-merge decode, compression, elastic,
mini dry-run) — run in SUBPROCESSES so the main pytest process keeps the
default single-device backend (the 512-device flag is dry-run-only)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def run_with_devices(code: str, n: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, env=env,
                          timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_moe_ep_matches_dense():
    out = run_with_devices("""
        import json, jax, jax.numpy as jnp
        from repro.configs.base import MoEConfig
        from repro.models.layers import moe as M
        from repro.distributed.sharding import ShardingRules, use_rules
        cfg = MoEConfig(num_experts=8, top_k=2, d_ff_expert=32,
                        capacity_factor=8.0)
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        params = {"router": jax.random.normal(ks[0], (16, 8)) * 0.1,
                  "w_gate": jax.random.normal(ks[1], (8, 16, 32)) * 0.1,
                  "w_up": jax.random.normal(ks[2], (8, 16, 32)) * 0.1,
                  "w_down": jax.random.normal(ks[3], (8, 32, 16)) * 0.1}
        x = jax.random.normal(ks[4], (2, 12, 16))
        idx, prob, _ = M.route(cfg, params, x)
        ref = M.moe_dense(cfg, params, x, idx, prob)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        rules = ShardingRules({"batch": ("data",), "seq_model": "model",
                               "experts": "model", "embed_act": None,
                               "seq": None})
        with mesh, use_rules(rules, mesh):
            y = jax.jit(lambda *a: M.moe_apply(cfg, *a))(params, x, idx, prob)
        print(json.dumps({"err": float(jnp.abs(y - ref).max())}))
    """)
    assert out["err"] < 1e-5


def test_lse_merge_decode_matches_local():
    out = run_with_devices("""
        import json, jax, jax.numpy as jnp
        from repro.distributed.sharding import ShardingRules, use_rules
        from repro.distributed.collectives import seq_sharded_decode_attention
        B, S, H, K, D = 4, 32, 8, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        q = jax.random.normal(ks[0], (B, 1, H, D))
        ck = jax.random.normal(ks[1], (B, S, K, D))
        cv = jax.random.normal(ks[2], (B, S, K, D))
        nk = jax.random.normal(ks[3], (B, 1, K, D))
        nv = jax.random.normal(ks[4], (B, 1, K, D))
        lengths = jnp.array([5, 17, 31, 24], jnp.int32)
        ref, rk, rv = seq_sharded_decode_attention(q, ck, cv, nk, nv, lengths)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        rules = ShardingRules({"batch": ("data",), "kv_seq": "model"})
        with mesh, use_rules(rules, mesh):
            o, k2, v2 = jax.jit(
                lambda *a: seq_sharded_decode_attention(*a))(
                q, ck, cv, nk, nv, lengths)
        print(json.dumps({
            "out": float(jnp.abs(o - ref).max()),
            "k": float(jnp.abs(k2 - rk).max()),
        }))
    """)
    assert out["out"] < 1e-5 and out["k"] == 0.0


def test_mini_dryrun_smoke_cell():
    """Lower+compile a smoke train step on an 8-device (2,4) mesh; verify
    memory analysis exists and collectives appear in the HLO."""
    out = run_with_devices("""
        import json, jax
        from repro.configs import registry as R
        from repro.configs.base import ShapeConfig
        from repro.configs.specs import abstract_params, input_specs
        from repro.distributed import policy
        from repro.distributed.sharding import rules_for, use_rules
        from repro.optim.optimizers import make_optimizer
        from repro.training.train_step import make_train_step
        cfg = R.smoke("qwen2.5-3b")
        shape = ShapeConfig("mini", "train", 64, 8)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        rules = rules_for(cfg, shape, mesh)
        opt = make_optimizer(cfg)
        step = make_train_step(cfg, opt, accum=2)
        p_sds = abstract_params(cfg)
        o_sds = jax.eval_shape(opt.init, p_sds)
        batch, _ = input_specs(cfg, shape)
        with mesh, use_rules(rules, mesh):
            jitted = jax.jit(
                step,
                in_shardings=(policy.param_shardings(cfg, mesh, rules),
                              policy.opt_state_shardings(cfg, opt, mesh, rules),
                              policy.batch_shardings(batch, mesh, rules)),
                donate_argnums=(0, 1))
            compiled = jitted.lower(p_sds, o_sds, batch).compile()
        ma = compiled.memory_analysis()
        txt = compiled.as_text()
        print(json.dumps({
            "temp": ma.temp_size_in_bytes,
            "has_allreduce": "all-reduce" in txt,
        }))
    """)
    assert out["temp"] > 0
    assert out["has_allreduce"]


def test_compressed_pod_mean_and_elastic():
    out = run_with_devices("""
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.optim.compression import compressed_cross_pod_mean
        from repro.distributed.elastic import surviving_mesh, reshard, shrink_batch
        from repro.distributed.sharding import ShardingRules
        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        g = jax.random.normal(jax.random.PRNGKey(0), (2, 64, 32))
        mean, err = compressed_cross_pod_mean(
            {"w": g}, {"w": jnp.zeros_like(g)}, mesh)
        exact = jnp.mean(g, axis=0)
        rel = float(jnp.abs(mean["w"] - exact).max() / jnp.abs(exact).max())
        mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                              axis_types=(jax.sharding.AxisType.Auto,)*2)
        nm = surviving_mesh(mesh2, {mesh2.devices[2, 1].id})
        print(json.dumps({"rel": rel, "rows": nm.devices.shape[0],
                          "batch": shrink_batch(48, 4, nm.devices.shape[0])}))
    """)
    assert out["rel"] < 0.02
    assert out["rows"] == 3 and out["batch"] == 36
