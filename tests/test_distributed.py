"""Sharding rules, tree collectives (subprocess, 8 devices), threshold
sync semantics, gossip baseline."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.distributed import sharding as shd
from repro.distributed import threshold_sync as TS
from repro.distributed.gossip_sync import agreement_error, gossip_round
from repro.models.model import abstract_params


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_param_specs_cover_structure(arch):
    """Spec pytree structure matches the param pytree exactly."""
    cfg = get_config(arch)
    params = abstract_params(cfg)
    specs = shd.param_specs(cfg)
    # tree.map raises on structure mismatch; also check rank compatibility
    def check(sp, leaf):
        assert isinstance(sp, P)
        assert len(sp) <= len(leaf.shape), (sp, leaf.shape)
        return sp

    jax.tree.map(check, specs, params, is_leaf=lambda x: isinstance(x, P))


def test_sanitize_drops_indivisible():
    class FakeMesh:
        shape = {"model": 16, "data": 16}

    specs = {"a": P(None, "model"), "b": P("model", None)}
    abs_tree = {
        "a": jax.ShapeDtypeStruct((4, 2731), jnp.float32),
        "b": jax.ShapeDtypeStruct((256, 4), jnp.float32),
    }
    out = shd.sanitize(specs, abs_tree, FakeMesh())
    assert out["a"] == P(None, None)
    assert out["b"] == P("model", None)


def test_zero1_shards_largest_divisible_dim():
    class FakeMesh:
        shape = {"model": 4, "data": 8}

    pspecs = {"w": P(None, "model")}
    abs_tree = {"w": jax.ShapeDtypeStruct((64, 128), jnp.float32)}
    out = shd.opt_state_specs(pspecs, abs_tree, FakeMesh(), zero1=True)
    assert out["m"]["w"] == P("data", "model")
    assert out["count"] == P()


_COLLECTIVE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import warnings; warnings.simplefilter("ignore")
    import jax, numpy as np
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map as sm
    from repro.core.tree_collectives import (
        tree_all_reduce, tree_broadcast, tree_reduce)
    mesh = jax.make_mesh((8,), ("pod",))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 4)), jnp.float32)
    ar = sm(lambda v: tree_all_reduce(v, "pod", 8), mesh=mesh,
            in_specs=P("pod"), out_specs=P("pod"), check_vma=False)
    got = np.asarray(ar(x))
    want = np.tile(np.asarray(x).reshape(8, 2, 4).sum(0), (8, 1)).reshape(16, 4)
    assert np.allclose(got, want, atol=1e-5), "tree_all_reduce != sum"
    # equality with psum
    ps = sm(lambda v: jnp.broadcast_to(jax.lax.psum(v, "pod"), v.shape),
            mesh=mesh, in_specs=P("pod"), out_specs=P("pod"), check_vma=False)
    assert np.allclose(np.asarray(ps(x)), got, atol=1e-5), "tree != psum"
    # broadcast distributes the root's shard
    bc = sm(lambda v: tree_broadcast(v, "pod", 8), mesh=mesh,
            in_specs=P("pod"), out_specs=P("pod"), check_vma=False)
    got_b = np.asarray(bc(x)).reshape(8, 2, 4)
    for i in range(8):
        assert np.allclose(got_b[i], np.asarray(x)[:2]), "broadcast wrong"
    print("COLLECTIVES_OK")
""")


def test_tree_collectives_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _COLLECTIVE_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=300)
    assert "COLLECTIVES_OK" in r.stdout, r.stdout + r.stderr


def test_threshold_sync_drift_votes_and_reset():
    params = {"w": jnp.ones((4, 8))}
    g = 4
    pg = TS.replicate_for_pods(params, g)
    cfg = TS.ThresholdSyncConfig(tau=0.1)
    outer = TS.init_outer_state(params, cfg)
    drift, votes = TS.drift_and_votes(pg, outer["agreement"], cfg)
    assert drift.shape == (g,) and float(drift.max()) == 0.0
    assert float(votes.sum()) == 0.0
    # perturb one pod past tau
    pg2 = jax.tree.map(lambda t: t.at[2].add(0.5), pg)
    drift, votes = TS.drift_and_votes(pg2, outer["agreement"], cfg)
    assert float(votes[2]) == 1.0 and float(votes[:2].sum()) == 0.0
    # sync averages the deltas and resets replicas to the new agreement
    sync = TS.make_sync_step(
        TS.ThresholdSyncConfig(tau=0.1, outer_lr=1.0, outer_momentum=0.0,
                               nesterov=False), g)
    pg3, outer2, m = sync(pg2, outer)
    want = 1.0 + 0.5 / g  # mean delta applied with outer_lr=1
    np.testing.assert_allclose(np.asarray(pg3["w"][0]), want, atol=1e-6)
    for i in range(g):
        np.testing.assert_allclose(np.asarray(pg3["w"][i]),
                                   np.asarray(pg3["w"][0]))
    d2, v2 = TS.drift_and_votes(pg3, outer2["agreement"], cfg)
    assert float(d2.max()) < 1e-6  # violation resolved — paper's invariant


def test_threshold_sync_compression_accounting():
    params = {"w": jnp.zeros((64,))}
    g = 2
    pg = TS.replicate_for_pods(params, g)
    pg = jax.tree.map(lambda t: t.at[0, :4].add(1.0), pg)  # sparse delta
    cfg = TS.ThresholdSyncConfig(tau=0.0, compress_tau=0.1, outer_lr=1.0,
                                 outer_momentum=0.0, nesterov=False)
    outer = TS.init_outer_state(params, cfg)
    sync = TS.make_sync_step(cfg, g)
    pg2, outer2, m = sync(pg, outer)
    assert float(m["sync_sent_bytes"]) == 4 * 4.0  # only 4 coords crossed tau


def test_gossip_converges_to_mean():
    params = {"w": jnp.arange(8.0)[:, None] * jnp.ones((8, 4))}
    e0 = float(agreement_error(params))
    p = params
    for r in range(3):  # log2(8) rounds of hypercube averaging
        p = gossip_round(p, r, 8)
    e1 = float(agreement_error(p))
    assert e1 < 1e-5 < e0
    np.testing.assert_allclose(np.asarray(p["w"][0]), 3.5, atol=1e-6)


def test_gossip_partial_rounds_reduce_error_monotonically():
    rngv = jnp.asarray(np.random.default_rng(0).standard_normal((16, 6)),
                       jnp.float32)
    p = {"w": rngv}
    errs = [float(agreement_error(p))]
    for r in range(4):
        p = gossip_round(p, r, 16)
        errs.append(float(agreement_error(p)))
    assert all(b < a + 1e-9 for a, b in zip(errs, errs[1:]))


_MOE_EP_SCRIPT = os.path.join(os.path.dirname(__file__), "_moe_ep_script.py")


def test_moe_ep_matches_gather_impl():
    """EP all-to-all MoE (H3) must be numerically exact vs the gather impl,
    values and gradients, on a real multi-device mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, _MOE_EP_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=560)
    assert "MOE_EP_OK" in r.stdout, r.stdout + r.stderr
