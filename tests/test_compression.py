"""1-bit compressed allreduce tests — the TPU analog of the reference's
tests/onebit/test_nccl_backend.py (compressed allreduce vs dense allreduce)
on a forced multi-device CPU mesh."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import compression as comp
from jax import shard_map


def _mesh(n):
    from jax.sharding import Mesh
    devs = jax.devices()[:n]
    if len(devs) < n:
        pytest.skip(f"need {n} devices")
    return Mesh(np.asarray(devs), ("data",))


def test_pack_unpack_roundtrip():
    x = jnp.asarray(np.random.RandomState(0).randn(64).astype(np.float32))
    signs = comp.unpack_signs(comp.pack_signs(x))
    np.testing.assert_array_equal(np.asarray(signs),
                                  np.where(np.asarray(x) >= 0, 1.0, -1.0))


def test_padded_numel():
    assert comp.padded_numel(1, 4) == 32
    assert comp.padded_numel(32, 4) == 32
    assert comp.padded_numel(33, 4) == 64


_RUN_CACHE = {}


def _run_allreduce(mesh, bufs, wes, ses):
    # build+jit the shard_map program once per mesh: rebuilding the closure
    # per call would recompile on every loop iteration
    key = id(mesh)
    if key not in _RUN_CACHE:
        @jax.jit
        @functools.partial(shard_map, mesh=mesh,
                           in_specs=(P("data"), P("data"), P("data")),
                           out_specs=(P("data"), P("data"), P("data")))
        def run(buf, we, se):
            out, we2, se2 = comp.compressed_allreduce(
                buf[0], we[0], se[0], "data")
            return out[None], we2[None], se2[None]
        _RUN_CACHE[key] = run
    return _RUN_CACHE[key](bufs, wes, ses)


def test_compressed_allreduce_approximates_mean():
    n, numel = 4, 256
    mesh = _mesh(n)
    rng = np.random.RandomState(1)
    bufs = jnp.asarray(rng.randn(n, numel).astype(np.float32))
    wes = jnp.zeros((n, numel), jnp.float32)
    ses = jnp.zeros((n, numel // n), jnp.float32)

    out, we2, se2 = _run_allreduce(mesh, bufs, wes, ses)
    out = np.asarray(out)
    # identical result on every device
    for i in range(1, n):
        np.testing.assert_array_equal(out[0], out[i])
    exact = np.asarray(bufs).mean(axis=0)
    # 1-bit quantization is coarse on one shot, but signs of large entries
    # must mostly agree and magnitude must be in the right ballpark
    big = np.abs(exact) > np.abs(exact).mean()
    agree = (np.sign(out[0][big]) == np.sign(exact[big])).mean()
    assert agree > 0.8, agree
    # errors are recorded (non-zero) and bounded
    assert float(jnp.abs(we2).max()) > 0
    assert np.isfinite(np.asarray(we2)).all()
    assert np.isfinite(np.asarray(se2)).all()


def test_error_feedback_drives_accumulated_mean_to_exact():
    """With a CONSTANT input, error feedback makes the time-average of the
    compressed result converge to the true mean (the error-compensation
    contract of the reference backend)."""
    n, numel = 4, 256   # same shapes as the test above → shared compile
    mesh = _mesh(n)
    rng = np.random.RandomState(2)
    bufs = jnp.asarray(rng.randn(n, numel).astype(np.float32))
    exact = np.asarray(bufs).mean(axis=0)

    wes = jnp.zeros((n, numel), jnp.float32)
    ses = jnp.zeros((n, numel // n), jnp.float32)
    acc = np.zeros(numel, np.float64)
    steps = 60
    for _ in range(steps):
        out, wes, ses = _run_allreduce(mesh, bufs, wes, ses)
        acc += np.asarray(out[0], np.float64)
    avg = acc / steps
    err = np.abs(avg - exact).max() / (np.abs(exact).max() + 1e-9)
    assert err < 0.15, err


def test_tree_allreduce_shapes_and_padding():
    n = 4
    mesh = _mesh(n)
    tree = {"a": jnp.ones((4, 8)), "b": jnp.full((2,), -1.0)}
    wes, ses = comp.init_error_states(tree, n)
    assert wes["a"].shape == (comp.padded_numel(32, n),)
    assert ses["b"].shape == (comp.padded_numel(2, n) // n,)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(), P(), P()), out_specs=(P(), P("data"), P("data")),
        check_vma=False)
    def run(tree, wes, ses):
        wes = jax.tree_util.tree_map(lambda x: x[0], wes)
        ses = jax.tree_util.tree_map(lambda x: x[0], ses)
        out, we2, se2 = comp.tree_compressed_allreduce(
            tree, wes, ses, "data")
        bump = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: x[None], t)
        return out, bump(we2), bump(se2)

    wes_b = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), wes)
    ses_b = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), ses)
    out, _, _ = run(tree, wes_b, ses_b)
    assert out["a"].shape == (4, 8)
    assert out["b"].shape == (2,)
    # "a" needs no padding: a constant-sign constant-magnitude buffer
    # round-trips 1-bit compression exactly (scale == the constant)
    np.testing.assert_allclose(np.asarray(out["a"]), np.ones((4, 8)),
                               rtol=1e-5)
    # "b" is padded 2→32; the padding zeros dilute the one-shot scale
    # (error feedback recovers it over steps) — only the sign is exact here
    assert (np.asarray(out["b"]) < 0).all()


# ---------------------------------------------------------------------------
# hierarchical link-aware exchange (ISSUE 10)
# ---------------------------------------------------------------------------

def _hier_mesh(inter, intra):
    from jax.sharding import Mesh
    n = inter * intra
    devs = jax.devices()[:n]
    if len(devs) < n:
        pytest.skip(f"need {n} devices")
    return Mesh(np.asarray(devs).reshape(inter, intra),
                ("data_inter", "data_intra"))


def test_hierarchical_allreduce_matches_flat_mean():
    """The uncompressed two-level path (fast-axis ring RS/AG around a
    slow-axis pmean of the chunk) is exact: it must match the flat mean
    over all devices to fp32 ring-order rounding."""
    inter, intra, numel = 2, 4, 128
    n = inter * intra
    mesh = _hier_mesh(inter, intra)
    rng = np.random.RandomState(3)
    bufs = jnp.asarray(rng.randn(n, numel).astype(np.float32))

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=P(("data_inter", "data_intra")),
        out_specs=P(("data_inter", "data_intra")), check_vma=False)
    def run(buf):
        return comp.hierarchical_allreduce(
            buf[0], "data_inter", "data_intra")[None]

    out = np.asarray(run(bufs))
    exact = np.asarray(bufs).mean(axis=0)
    for i in range(n):
        np.testing.assert_allclose(out[i], exact, rtol=1e-5, atol=1e-6)


def test_hierarchical_compressed_matches_flat_compressed_quality():
    """The hierarchical 1-bit exchange approximates the global mean with
    the same one-shot quality contract as the flat compressed path
    (sign agreement on large entries) and yields the identical result
    on every device."""
    inter, intra = 2, 4
    n = inter * intra
    numel = 512            # divisible by 8*inter*intra
    mesh = _hier_mesh(inter, intra)
    rng = np.random.RandomState(4)
    bufs = jnp.asarray(rng.randn(n, numel).astype(np.float32))
    wes = jnp.zeros((n, numel // intra), jnp.float32)
    ses = jnp.zeros((n, numel // n), jnp.float32)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(("data_inter", "data_intra")),) * 3,
        out_specs=(P(("data_inter", "data_intra")),) * 3, check_vma=False)
    def run(buf, we, se):
        out, we2, se2 = comp.hierarchical_compressed_allreduce(
            buf[0], we[0], se[0], "data_inter", "data_intra")
        return out[None], we2[None], se2[None]

    out, we2, se2 = run(bufs, wes, ses)
    out = np.asarray(out)
    for i in range(1, n):
        np.testing.assert_array_equal(out[0], out[i])
    exact = np.asarray(bufs).mean(axis=0)
    big = np.abs(exact) > np.abs(exact).mean()
    agree = (np.sign(out[0][big]) == np.sign(exact[big])).mean()
    assert agree > 0.8, agree
    assert float(jnp.abs(we2).max()) > 0
    assert np.isfinite(np.asarray(we2)).all()
    assert np.isfinite(np.asarray(se2)).all()


def test_hierarchical_error_feedback_converges():
    """Error feedback over the slow hop only: with a constant input the
    time-average of the hierarchical compressed result converges to the
    true mean (same contract as the flat exchange — the uncompressed
    fast hop must not break the compensation loop)."""
    inter, intra = 2, 4
    n = inter * intra
    numel = 512
    mesh = _hier_mesh(inter, intra)
    rng = np.random.RandomState(5)
    bufs = jnp.asarray(rng.randn(n, numel).astype(np.float32))
    exact = np.asarray(bufs).mean(axis=0)

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(("data_inter", "data_intra")),) * 3,
        out_specs=(P(("data_inter", "data_intra")),) * 3, check_vma=False)
    def run(buf, we, se):
        out, we2, se2 = comp.hierarchical_compressed_allreduce(
            buf[0], we[0], se[0], "data_inter", "data_intra")
        return out[None], we2[None], se2[None]

    wes = jnp.zeros((n, numel // intra), jnp.float32)
    ses = jnp.zeros((n, numel // n), jnp.float32)
    acc = np.zeros(numel, np.float64)
    steps = 60
    for _ in range(steps):
        out, wes, ses = run(bufs, wes, ses)
        acc += np.asarray(out[0], np.float64)
    avg = acc / steps
    err = np.abs(avg - exact).max() / (np.abs(exact).max() + 1e-9)
    assert err < 0.15, err


def test_bucketed_hierarchical_policy_and_wire_bytes():
    """Per-bucket policy + the trace-time cost model: 'never' must be
    bit-comparable to the exact two-level mean, 'auto' compresses only
    buckets over the byte floor, and the modeled slow-hop bytes drop
    >= 4x when compression is on."""
    from deepspeed_tpu.parallel import overlap
    inter, intra = 2, 4
    mesh = _hier_mesh(inter, intra)
    n = inter * intra
    plan = lambda policy, floor=0: overlap.HierarchyPlan(  # noqa: E731
        inter_axis="data_inter", intra_axis="data_intra",
        inter=inter, intra=intra, compression=policy,
        min_bucket_bytes=floor, bucket_elems=200)
    tree = {"a": jnp.asarray(np.random.RandomState(6).randn(16, 16),
                             jnp.float32),
            "b": jnp.asarray(np.random.RandomState(7).randn(40),
                             jnp.float32)}
    leaves = jax.tree_util.tree_leaves(tree)
    shapes = [l.shape for l in leaves]
    buckets = overlap.plan_buckets(shapes, 200, n)
    assert len(buckets) == 2     # 256-elem leaf overflows the 200 budget

    # auto with a floor between the two buckets compresses only the big
    flags = overlap.plan_bucket_compression(
        buckets, plan("auto", floor=256 * 4))
    assert flags == [True, False], (flags, [b.padded for b in buckets])

    wire_on = overlap.hierarchy_wire_bytes(buckets, [True, True],
                                           plan("always"))
    wire_off = overlap.hierarchy_wire_bytes(buckets, [False, False],
                                            plan("never"))
    assert wire_off["inter"] == wire_off["inter_uncompressed"]
    assert wire_on["inter_uncompressed"] >= 4 * wire_on["inter"], wire_on

    # 'never' policy: the bucketed exchange equals the exact flat mean
    p = plan("never")
    wes, ses = overlap.hierarchical_error_states(tree, p)
    assert wes == [None, None]   # nothing compressed -> no error state

    @jax.jit
    @functools.partial(shard_map, mesh=mesh, in_specs=P(),
                       out_specs=P(), check_vma=False)
    def run_never(tree):
        out, _, _ = overlap.bucketed_hierarchical_compressed_allreduce(
            tree, [None, None], [None, None], p)
        return out

    out = run_never(tree)   # replicated input -> mean is the input
    for got, want in zip(jax.tree_util.tree_leaves(out), leaves):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
