"""Tests for the measurement machinery itself (analysis.hlo / roofline) —
wrong meters are worse than no meters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import (
    collective_bytes, flops_and_bytes, loop_scales, xla_cost,
)


def test_scan_flops_scale_with_trip_count():
    """The reason analysis.hlo exists: XLA cost_analysis counts while
    bodies once; our walker must scale by trip count exactly."""

    def scanned(x, ws):
        def body(c, w):
            return c @ w, None

        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jnp.zeros((256, 256))
    ws = jnp.zeros((10, 256, 256))
    comp = jax.jit(scanned).lower(x, ws).compile()
    xla = xla_cost(comp)["flops"]
    ours = flops_and_bytes(comp.as_text())["flops"]
    want = 10 * 2 * 256 ** 3
    assert xla == pytest.approx(want / 10)  # the documented XLA behaviour
    assert ours == pytest.approx(want)


def test_nested_scan_scales_multiply():
    def inner(c, w):
        def body(c2, w2):
            return c2 @ w2, None

        y, _ = jax.lax.scan(body, c, w)
        return y, None

    def outer(x, ws):
        y, _ = jax.lax.scan(inner, x, ws)
        return y

    x = jnp.zeros((64, 64))
    ws = jnp.zeros((3, 4, 64, 64))  # 3 outer x 4 inner = 12 matmuls
    txt = jax.jit(outer).lower(x, ws).compile().as_text()
    fb = flops_and_bytes(txt)
    assert fb["flops"] == pytest.approx(12 * 2 * 64 ** 3)
    # the inner body is a >=2-deep nested scope -> kernel-scope attribution
    assert fb["kernel_scope_flops"] == pytest.approx(12 * 2 * 64 ** 3)


def test_collective_bytes_sees_psum():
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import warnings; warnings.simplefilter("ignore")
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.analysis.hlo import collective_bytes
        mesh = jax.make_mesh((4,), ("d",))
        f = shard_map(lambda v: jax.lax.psum(v, "d"), mesh=mesh,
                      in_specs=P("d"), out_specs=P(), check_vma=False)
        txt = jax.jit(f).lower(jnp.zeros((64, 128), jnp.float32)).compile().as_text()
        cb = collective_bytes(txt)
        want = 16 * 128 * 4  # per-device shard bytes
        assert abs(cb.get("all-reduce", 0) - want) < want, cb
        print("CB_OK", cb)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, timeout=300)
    assert "CB_OK" in r.stdout, r.stdout + r.stderr


def test_roofline_rows_sane_on_recorded_cells():
    import glob
    import json

    from repro.analysis.roofline import roofline_row

    recs = [json.load(open(f)) for f in
            sorted(glob.glob("results/dryrun/*__sp.json"))]
    if not recs:
        pytest.skip("no dry-run records present")
    n_rows = 0
    for r in recs:
        row = roofline_row(r)
        if row is None:
            continue
        n_rows += 1
        for k in ("t_compute_s", "t_mem_kernel_s", "t_collective_s"):
            assert row[k] >= 0
        assert row["dominant"] in ("compute", "memory", "collective")
        assert 0 <= row["roofline_mfu"] <= 1
        assert row["useful_ratio"] > 0
    assert n_rows >= 30  # 32 OK cells expected


def test_active_params_moe_counts_topk_only():
    from repro.analysis.roofline import active_params
    from repro.configs.registry import get_config

    dense = active_params(get_config("gemma-7b"))
    assert 7e9 < dense < 10e9
    ds = get_config("deepseek-v3-671b")
    act = active_params(ds)
    # DeepSeek-V3: ~37B active of 671B total
    assert 2.5e10 < act < 5.5e10, act
