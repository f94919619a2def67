"""The residual streams' kernels (``models/hyper_connections.py``,
``ops/pallas/mhc_stream.py``) in the interpreter: every entry's value and
every gradient against the ``jnp`` forms that stand beside them, each entry
alone and the three of a branch together, under remat, and the fall to the
``jnp`` forms; the looped walks against the unrolled ones; and what the
kernels cost a program BEFORE it runs — a pass traced once however many
branches call it, one function of the lowered module a pass."""

import collections
import dataclasses
import functools
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import hyper_connections as hc
from deepspeed_tpu.models.gpt2 import block_remat_policy
from deepspeed_tpu.ops.pallas import mhc_stream as kernels
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.telemetry.registry import default_registry
from tests.perf import mhc_stream_bench as bench

F32 = jnp.float32
N = 4
# three row tiles of 128 tokens, two batch rows
B, S = 2, 192
# a float32 kernel differs from the float32 form by the order of its sums
# alone; at bf16 the kernels' projection takes phi in bf16 (what a TPU's
# default float32 product does to the jnp form; the CPU's keeps it whole)
LIMIT = {"float32": 2e-5, "bfloat16": 1.5e-2}
MIXER = hc.StreamMixer(n=N, phi_std=0.1, gate_mean=0.5, gate_std=0.1,
                       bias_std=0.5)


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def draw(C, dtype=F32, batch=B, seq=S):
    kx, ky, kp = jax.random.split(jax.random.PRNGKey(C), 3)
    x = jax.random.normal(kx, (batch, seq, N * C), F32).astype(dtype)
    y = jax.random.normal(ky, (batch, seq, C), F32).astype(dtype)
    params = jax.jit(lambda x: MIXER.init(kp, x))(x)["params"]
    return params, x, y


def sites():
    """(``mhc/kernel_sites``, ``mhc/xla_sites``) as the registry has them."""
    gauges = default_registry().snapshot(prefix="mhc/")["gauges"]
    return (gauges.get("mhc/kernel_sites", 0), gauges.get("mhc/xla_sites", 0))


def _coefficients(params, x):
    return MIXER.apply({"params": params}, x, mutable=["stats"])[0]


def _coefficients_jnp(params, x):
    return hc.coefficients_jnp(
        x, params["phi"], params["gate"], params["bias"], N, MIXER.eps,
        MIXER.clamp, MIXER.sinkhorn_iters)


def branch(params, x, y):
    """A branch through the entries: the kernels where the shapes allow.
    Everything a caller can hold: (X_new, u, H_pre, H_post, H_res)."""
    (u, coeff, through), _ = nn.apply(
        hc.mix, MIXER, mutable=["stats"])({"params": params}, x)
    mixed = y * 0.5 + u * u.astype(F32).mean().astype(u.dtype)
    return (hc.write(through, mixed, coeff[1], coeff[2]), u, *coeff)


def branch_jnp(params, x, y):
    coeff = _coefficients_jnp(params, x)
    u = hc.read(x, coeff[0])
    mixed = y * 0.5 + u * u.astype(F32).mean().astype(u.dtype)
    return (hc.write_jnp(x, mixed, coeff[1], coeff[2]), u, *coeff)


def scalar(fn):
    """A scalar of everything ``fn`` gives, fixed weights a result."""
    def loss(*args):
        outs = fn(*args)
        keys = jax.random.split(jax.random.PRNGKey(7), len(outs))
        return sum(jnp.sum(o.astype(F32) * jax.random.normal(k, o.shape, F32))
                   for o, k in zip(outs, keys))
    return loss


NAMES = ("X_new", "u", "H_pre", "H_post", "H_res")


@pytest.mark.parametrize("C,dtype", [(128, "float32"), (256, "float32"),
                                     (128, "bfloat16")])
def test_a_branch_through_the_kernels_gives_the_jnp_forms_values(C, dtype):
    params, x, y = draw(C, jnp.dtype(dtype))
    before = sites()
    got = jax.jit(branch)(params, x, y)
    after = sites()
    # mix and write each count a site, and none fell to the jnp form
    assert (after[0] - before[0], after[1] - before[1]) == (2, 0)
    want = jax.jit(branch_jnp)(params, x, y)
    for name, a, b in zip(NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, b) < LIMIT[dtype], (name, rel(a, b))


@pytest.mark.parametrize("C,dtype", [(128, "float32"), (256, "float32"),
                                     (128, "bfloat16")])
def test_a_branch_through_the_kernels_gives_the_jnp_forms_gradients(C, dtype):
    """dX, dy, dphi, dgate and dbias of a scalar of BOTH outputs and of the
    coefficients: the stream's three cotangents (through ``write``, through
    ``u``, through the norm and the projection) leave as one array."""
    params, x, y = draw(C, jnp.dtype(dtype))
    got = jax.jit(jax.grad(scalar(branch), argnums=(0, 1, 2)))(params, x, y)
    want = jax.jit(jax.grad(scalar(branch_jnp), argnums=(0, 1, 2)))(
        params, x, y)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert rel(a, b) < LIMIT[dtype], (jax.tree_util.keystr(path),
                                          rel(a, b))


@pytest.mark.parametrize("entry", ["mixer", "write", "read_and_write"])
def test_every_entry_differentiates_correctly_alone(entry):
    """No rule leans on the other: the coefficients alone (``u`` and the
    stream handed on go unused), ``write`` on the stream itself, and
    ``read`` and ``write`` on the stream itself with XLA adding their
    cotangents."""
    params, x, y = draw(128)
    h_post = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3),
                                              (N, B * S)))
    h_res = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(4),
                                             (N, N, B * S)), axis=1)

    def fn(coefficients, write):
        def mixer(params, x, y):
            return coefficients(params, x)

        def write_alone(params, x, y):
            return (write(x, y, h_post, h_res),)

        def read_and_write(params, x, y):
            pre, post, res = coefficients(params, x)
            return (write(x, hc.read(x, pre) + y, post, res),)

        return {"mixer": mixer, "write": write_alone,
                "read_and_write": read_and_write}[entry]

    def grads(coefficients, write):
        return jax.jit(jax.grad(scalar(fn(coefficients, write)),
                                argnums=(0, 1, 2)))(params, x, y)

    got = grads(_coefficients, hc.write)
    want = grads(_coefficients_jnp, hc.write_jnp)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want)):
        if np.any(np.asarray(b)):
            assert rel(a, b) < LIMIT["float32"], (
                jax.tree_util.keystr(path), rel(a, b))
        else:       # what the entry does not read: no cotangent either way
            assert not np.any(np.asarray(a)), jax.tree_util.keystr(path)


@pytest.mark.parametrize("C,dtype", [(128, "float32"), (256, "bfloat16")])
def test_spread_and_merge_take_the_kernels_and_are_each_others_backward(
        C, dtype):
    """A trunk's two ends: the copy into the streams and their float32 sum,
    values and gradients against ``jnp.tile`` and the split's sum."""
    _, x, y = draw(C, jnp.dtype(dtype))

    def ends(x, y, spread, merge):
        return spread(y, N) * 0.5 + x, merge(x, N)

    def ends_jnp(x, y):
        return ends(x, y, lambda y, n: jnp.tile(y, (1, 1, n)),
                    lambda x, n: sum(jnp.split(x.astype(F32), n, axis=-1)
                                     ).astype(x.dtype))

    before = sites()
    got = jax.jit(lambda x, y: ends(x, y, hc.spread, hc.merge))(x, y)
    after = sites()
    assert (after[0] - before[0], after[1] - before[1]) == (2, 0)
    for a, b in zip(got, jax.jit(ends_jnp)(x, y)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert rel(a, b) < LIMIT[dtype] / 4
    got = jax.jit(jax.grad(scalar(lambda x, y: ends(
        x, y, hc.spread, hc.merge)), argnums=(0, 1)))(x, y)
    want = jax.jit(jax.grad(scalar(ends_jnp), argnums=(0, 1)))(x, y)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert rel(a, b) < LIMIT[dtype] / 4


@pytest.mark.parametrize("C,batch,seq,why", [
    (64, B, S, "streams of half a vreg row"),
    (128, 1, 200, "tokens the row tile does not divide")])
def test_a_shape_the_rule_refuses_takes_the_jnp_form(C, batch, seq, why):
    assert not kernels.takes(batch * seq, N, C), why
    params, x, y = draw(C, batch=batch, seq=seq)
    before = sites()
    got = jax.jit(branch)(params, x, y)
    after = sites()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 2)
    want = jax.jit(branch_jnp)(params, x, y)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_rule_takes_the_cells_shape_and_only_whole_tiles():
    assert kernels.takes(4096, 4, 3584) and kernels.takes(384, 4, 128)
    assert not kernels.takes(4096 + 64, 4, 3584)     # half a row tile over
    assert not kernels.takes(4096, 4, 3584 + 64)     # half a vreg row over
    assert not kernels.takes(4096, 1, 3584)          # one stream: no mixer
    assert not kernels.takes(4096, 11, 128)          # 143 coefficients
    assert kernels.StreamPlan(384, 4, 128, kernels.ROW_TILE, 20, 1e-6,
                              (-30.0, 30.0)).w == 24


class _Block(nn.Module):
    """Two branches on one stream, as a block of the model has them."""

    @nn.compact
    def __call__(self, x):
        for name in ("attn_hc", "ffn_hc"):
            u, (_, post, res), x = hc.mix(hc.StreamMixer(
                n=N, phi_std=0.1, gate_mean=0.5, gate_std=0.1, bias_std=0.5,
                name=name), x)
            x = hc.write(x, jnp.tanh(u), post, res)
        return x


def test_under_remat_the_gradients_are_the_unrematted_ones():
    _, x, _ = draw(128)
    plain = _Block()
    rematted = nn.remat(_Block, prevent_cse=True,
                        policy=block_remat_policy(None))()
    params = jax.jit(plain.init)(jax.random.PRNGKey(5), x)["params"]

    def grads(model):
        return jax.jit(jax.grad(lambda p, x: scalar(lambda p, x: (
            model.apply({"params": p}, x, mutable=["stats"])[0],))(p, x),
            argnums=(0, 1)))(params, x)

    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(grads(rematted)),
            jax.tree_util.tree_leaves(grads(plain))):
        assert rel(a, b) < 1e-6, (jax.tree_util.keystr(path), rel(a, b))


def test_on_a_mesh_of_several_devices_a_branch_takes_the_jnp_form():
    """Under an engine's mesh of two devices (``layout_pins``: how a trace
    knows it) a Mosaic call would not be partitioned: shapes the kernels take
    on one device fall to the ``jnp`` form, bit for bit its numbers."""
    if len(jax.devices()) < 2:
        pytest.skip("need 2 devices")
    assert kernels.takes(B * S, N, 128)
    params, x, y = draw(128)
    mesh = mesh_lib.make_mesh(mesh_lib.MeshConfig(data=2),
                              devices=jax.devices()[:2])
    before = sites()
    with mesh_lib.layout_pins(mesh):
        # a function of its own: ``jax.jit`` keys a trace on the function
        # and the shapes, not on the pinned mesh, and ``branch`` at these
        # shapes was traced by the tests above
        got = jax.jit(lambda *a: branch(*a))(params, x, y)
    after = sites()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 2)
    for a, b in zip(got, jax.jit(branch_jnp)(params, x, y)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------- looped walks, unrolled walks

@pytest.mark.parametrize("name", ["mix", "write", "write_backward",
                                  "mix_backward", "sum_streams"])
def test_a_looped_walk_gives_the_unrolled_walks_numbers(name, monkeypatch):
    """Five slabs a stream: a loop of two slabs a turn and one slab after
    it, a loop of one slab a turn, and all five written out (PR 57's form)
    add the same numbers in the same order. The pass is called under its
    ``jax.jit`` wrapper's skin (``__wrapped__``): the wrapper would hand back
    the first trace whatever ``SLABS_A_TURN`` says by then."""
    T, C = 2 * kernels.ROW_TILE, 5 * kernels.LANES
    plan = kernels.StreamPlan(T, N, C, kernels.ROW_TILE, 20, 1e-6,
                              (-30.0, 30.0))
    args = bench.randoms(bench.passes(T, C, F32)[name][0], 0.3)
    fn = getattr(kernels, name).__wrapped__

    def run(turn):
        monkeypatch.setitem(kernels.SLABS_A_TURN, name, turn)
        return jax.tree_util.tree_leaves(
            jax.jit(lambda *a: fn(*a, plan, True))(*args))

    unrolled = run(5)
    assert all(np.isfinite(np.asarray(a)).all() and np.any(np.asarray(a))
               for a in unrolled)
    for turn in (2, 1):
        for a, b in zip(run(turn), unrolled):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------ what the kernels cost before a program runs

KERNELS = ("_mhc_mix_kernel", "_mhc_write_kernel", "_mhc_write_bwd_kernel",
           "_mhc_mix_bwd_kernel", "_mhc_tile_kernel", "_mhc_sum_kernel")


def _tiny_model(layers):
    """The Xing4.0 family's model cut to ``layers`` dense blocks of four
    streams of 128 columns under ``remat_block`` (a shape the kernels take),
    no prediction module."""
    from benchmark import manifest
    from deepspeed_tpu.models.deepseek_v3 import DeepseekV3ForCausalLM
    bench = manifest.load()
    config = manifest.config_of(
        bench, manifest.cell_of(bench, "xing4-train-1chip-s4096"))
    cfg = dataclasses.replace(
        manifest.family_module(config).model_config(config, True),
        hidden_size=128, num_hidden_layers=layers,
        first_k_dense_replace=layers, num_nextn_predict_layers=0,
        remat=True, loss_chunk=0)
    assert cfg.hc_mult == N
    return DeepseekV3ForCausalLM(cfg)


def _lowered_for_a_tpu(layers, monkeypatch):
    """The text of the ``layers``-block model's loss and gradient lowered
    for a TPU from here (``lowering_platforms``: Mosaic lowers without a
    chip), the kernel form and not the interpreter."""
    monkeypatch.setattr(hc, "is_tpu_backend", lambda: True)
    model = _tiny_model(layers)
    ids = jax.ShapeDtypeStruct((1, kernels.ROW_TILE), jnp.int32)
    params = jax.eval_shape(
        lambda ids: model.init(jax.random.PRNGKey(0), ids), ids)["params"]

    def loss(params, ids):
        return model.apply({"params": params}, ids, labels=ids,
                           mutable=["stats"])[0]

    return jax.jit(jax.value_and_grad(loss)).trace(params, ids).lower(
        lowering_platforms=("tpu",)).as_text()


def _definitions(text):
    return collections.Counter(re.findall(r'kernel_name = "(\w+)"', text))


def _sites(text):
    """Calls of the passes' functions in the module's text."""
    return collections.Counter(
        name for name in re.findall(r"call @(\w+?)(?:_\d+)?\(", text)
        if name in ("mix", "write", "write_backward", "mix_backward", "tile",
                    "sum_streams"))


def test_a_pass_is_traced_once_and_is_one_function_however_many_call_it(
        monkeypatch):
    """What PR 57 was refused for, held: a model of one block (two branches)
    and of three (six) lowered for a TPU. A kernel's body is traced when its
    pass first meets a shape in a tracing context (at most two: where JAX's
    backward pass evaluates an equation it sets an EMPTY abstract mesh, the
    forward trace has none, and ``jax.jit`` keys its traces on that) and
    never again — not in the second branch, not in the recomputation, not in
    the next program; and the module defines
    each backward kernel once and each forward kernel once for the forward
    pass and once for the recomputation (JAX's partial evaluation of a
    rematted block makes those two functions), whatever the number of blocks
    and branches that call them."""
    traces = collections.Counter()
    for name in KERNELS:
        def counted(*refs, _fn=getattr(kernels, name), **kw):
            traces[_fn.__name__] += 1
            return _fn(*refs, **kw)
        # under the kernel's own name: it names the Mosaic module
        monkeypatch.setattr(kernels, name, functools.wraps(
            getattr(kernels, name))(counted))
    # this test's shapes ([128, 4 x 128] bf16) are no other test's: the
    # passes' jit wrappers have not met them
    one = _lowered_for_a_tpu(1, monkeypatch)
    first = dict(traces)
    assert set(first) == set(KERNELS) and set(first.values()) <= {1, 2}, first
    three = _lowered_for_a_tpu(3, monkeypatch)
    assert dict(traces) == first
    want = {"_mhc_mix_kernel": 2, "_mhc_write_kernel": 2,
            "_mhc_write_bwd_kernel": 1, "_mhc_mix_bwd_kernel": 1,
            "_mhc_tile_kernel": 2, "_mhc_sum_kernel": 2}
    for text, blocks in ((one, 1), (three, 3)):
        assert dict(_definitions(text)) == want, blocks
        # mix: a block's two branches forward and recomputed; write: the
        # second branch's is the block's result and is not recomputed
        assert dict(_sites(text)) == {
            "mix": 4 * blocks, "write": 3 * blocks,
            "write_backward": 2 * blocks, "mix_backward": 2 * blocks,
            "tile": 2, "sum_streams": 2}, blocks
