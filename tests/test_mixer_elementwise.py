"""The mixers' elementwise kernels (``ops/mixer_elementwise.py``,
``ops/pallas/mixer_elementwise.py``) in the interpreter: forward and every
gradient against the XLA forms that stand beside them and against float32
references written here, and the fall to the XLA forms."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import mixer_elementwise as entry
from deepspeed_tpu.ops.pallas import mixer_elementwise as kernels
from deepspeed_tpu.telemetry.registry import default_registry

F32 = jnp.float32


def conv_reference(x, taps, bias, offset, l2_scale, eps=entry.L2_EPS):
    """float32, written out: the convolution as a loop over the taps of a
    padded array, SiLU, the L2 norm over heads of 128."""
    W, C = taps.shape
    xf = x[..., offset:offset + C].astype(F32)
    S = xf.shape[1]
    xp = jnp.concatenate([jnp.zeros_like(xf[:, :W - 1]), xf], axis=1)
    p = sum(xp[:, j:j + S] * taps[j].astype(F32) for j in range(W))
    if bias is not None:
        p = p + bias.astype(F32)
    y = p * jax.nn.sigmoid(p)
    if l2_scale is not None:
        h = y.reshape(*y.shape[:2], C // 128, 128)
        h = h / jnp.sqrt(jnp.sum(h * h, axis=-1, keepdims=True) + eps)
        y = (h * l2_scale).reshape(y.shape)
    return y


def norm_reference(y, z, w, offset, group, eps, gate_first):
    B, S, D = y.shape
    gate = jax.nn.silu(z[..., offset:offset + D].astype(F32))
    u = y.astype(F32) * gate if gate_first else y.astype(F32)
    g = u.reshape(B, S, D // group, group)
    g = g / jnp.sqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    out = g.reshape(B, S, D) * jnp.tile(w.astype(F32), D // w.shape[0])
    return out if gate_first else out * gate


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


def draw(seed, *shapes, dtype=F32):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, F32).astype(dtype)
            for k, s in zip(keys, shapes)]


# bf16 rounds a result to 2^-9; a float32 kernel differs from the float32
# reference by the order of its sums alone
LIMIT = {"float32": 2e-5, "bfloat16": 6e-3}


@pytest.mark.parametrize("dtype,B,S,rows,bias,l2,offset,total,C", [
    ("float32", 1, 256, 128, False, None, 0, 256, 256),  # 2 blocks x 2 tiles
    # history resets at a sequence's start; an offset; a ragged wide array
    ("float32", 2, 128, 64, True, None, 128, 600, 256),
    ("float32", 2, 128, 64, False, 0.5, 0, 384, 256),    # q: norm and scale
    ("bfloat16", 2, 128, 64, True, None, 128, 600, 256),
    ("bfloat16", 1, 128, 64, True, 1.0, 256, 640, 128),  # bias, norm, offset
])
def test_conv_kernel_against_xla_and_reference(dtype, B, S, rows, bias, l2,
                                               offset, total, C):
    dt = jnp.dtype(dtype)
    x, dy = draw(1, (B, S, total), (B, S, C), dtype=dt)
    taps, b = draw(2, (4, C), (C,))
    taps, b = taps * 0.5, (b * 0.3 if bias else None)
    assert kernels.conv_takes(S, C, total, offset, 4, l2 and 128, rows)

    def kernel(x, taps, b):
        return kernels.conv_act_kernel(x, taps, b, offset=offset,
                                       l2_scale=l2, eps=entry.L2_EPS,
                                       interpret=True, block_rows=rows)

    def xla(x, taps, b):
        return entry.conv_act_xla(x, taps, b, offset=offset,
                                  runs=((C, l2),), head_width=128)[0]

    def reference(x, taps, b):
        return conv_reference(x, taps, b, offset, l2)

    def grads(fn):
        def loss(x, taps, b):
            out = fn(x, taps, b)
            return jnp.sum(out.astype(F32) * dy.astype(F32)), out
        (_, out), g = jax.value_and_grad(
            loss, argnums=(0, 1, 2) if bias else (0, 1), has_aux=True)(
            x, taps, b)
        return out, g

    (got, g_kernel), (want, g_ref) = grads(kernel), grads(reference)
    from_xla, g_xla = grads(xla)
    assert got.dtype == dt and got.shape == (B, S, C)
    assert rel(got, want) < LIMIT[dtype]
    # the XLA form multiplies and sums in the inputs' dtype: no nearer to
    # the reference than the kernel
    assert rel(got, want) <= rel(from_xla, want) + LIMIT[dtype] / 10
    for name, a, r, o in zip(("dx", "dtaps", "dbias"), g_kernel, g_ref,
                             g_xla):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        assert rel(a, r) < 2 * LIMIT[dtype], (name, rel(a, r))
        assert rel(a, r) <= rel(o, r) + LIMIT[dtype], (name, rel(o, r))
    # nothing flows into the columns the call does not read
    dx = np.asarray(g_kernel[0], np.float32)
    assert not dx[..., :offset].any() and not dx[..., offset + C:].any()


@pytest.mark.parametrize(
    "dtype,B,S,rows,group,gate_first,offset,total,D,shared", [
        ("float32", 1, 128, 64, 128, False, 256, 512, 256, True),  # Qwen3-Next
        ("float32", 2, 128, 64, 512, True, 0, 600, 512, False),    # Nemotron
        ("float32", 1, 256, 128, 128, True, 0, 128, 128, False),   # two tiles
        ("bfloat16", 1, 128, 64, 128, False, 256, 512, 256, True),
        ("bfloat16", 2, 128, 64, 512, True, 0, 600, 512, False),
        # a group wider than the widest column block is its own block, its
        # row tile walked in slabs of 512 twice: granite-4.0-h's ONE group
        # of all 4,096 channels, gate first, z the first columns of 8,512
        ("float32", 1, 128, 64, 4096, True, 0, 8512, 4096, False),
        ("bfloat16", 1, 128, 64, 4096, True, 0, 8512, 4096, False),
        # two groups of 1,024 (two column blocks), the gate at an offset
        ("float32", 2, 128, 128, 1024, True, 2048, 4224, 2048, False),
        # the gate after the norm, a group of 640: slabs of 128
        ("float32", 1, 64, 64, 640, False, 640, 1280, 640, True),
        ("bfloat16", 1, 64, 64, 1024, False, 1024, 2048, 1024, True),
    ])
def test_norm_kernel_against_xla_and_reference(dtype, B, S, rows, group,
                                               gate_first, offset, total, D,
                                               shared):
    dt = jnp.dtype(dtype)
    y, z, do = draw(3, (B, S, D), (B, S, total), (B, S, D), dtype=dt)
    w = 1.0 + 0.2 * draw(4, (group if shared else D,))[0]
    eps = 1e-5
    assert kernels.norm_takes(S, D, total, offset, group, rows)

    def kernel(y, z, w):
        return kernels.gated_group_norm_kernel(
            y, z, jnp.tile(w, D // w.shape[0]), group=group, eps=eps,
            gate_first=gate_first, offset=offset, interpret=True,
            block_rows=rows)

    def xla(y, z, w):
        return entry.gated_group_norm_xla(
            y, z, w, group=group, eps=eps, gate_first=gate_first,
            offset=offset)

    def reference(y, z, w):
        return norm_reference(y, z, w, offset, group, eps, gate_first)

    def grads(fn):
        def loss(y, z, w):
            out = fn(y, z, w)
            return jnp.sum(out.astype(F32) * do.astype(F32)), out
        (_, out), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(y, z, w)
        return out, g

    (got, g_kernel), (want, g_ref) = grads(kernel), grads(reference)
    from_xla, g_xla = grads(xla)
    assert got.dtype == dt and got.shape == (B, S, D)
    assert rel(got, want) < LIMIT[dtype]
    assert rel(from_xla, want) < LIMIT[dtype]
    for name, a, r, o in zip(("dy", "dz", "dw"), g_kernel, g_ref, g_xla):
        assert a.shape == r.shape and a.dtype == r.dtype, name
        assert rel(a, r) < 2 * LIMIT[dtype], (name, rel(a, r))
        assert rel(o, r) < 2 * LIMIT[dtype], (name, rel(o, r))


def sites():
    snap = default_registry().snapshot(prefix="mixer/")["gauges"]
    return {k.split("/")[1]: v for k, v in snap.items()}


def test_entries_take_the_kernels_and_cut_the_runs():
    """The entries as the two models call them: three runs out of a wide
    projection (q and k normalised), one weight shared by every head."""
    B, S, key, val = 2, 64, 256, 512
    qkvz, = draw(5, (B, S, 2 * key + 2 * val), dtype=jnp.bfloat16)
    taps, w = draw(6, (4, 2 * key + val), (128,))
    runs = ((key, 128 ** -0.5), (key, 1.0), (val, None))
    before = sites()
    q, k, v = entry.conv_act(qkvz, taps, runs=runs, head_width=128)
    oq, ok, ov = entry.conv_act_xla(qkvz, taps, runs=runs, head_width=128)
    assert [t.shape[-1] for t in (q, k, v)] == [key, key, val]
    for a, o in ((q, oq), (k, ok), (v, ov)):
        assert a.dtype == jnp.bfloat16 and rel(a, o) < 1e-2
    out = entry.gated_group_norm(v, qkvz, w, group=128, eps=1e-6,
                                 gate_first=False, offset=2 * key + val)
    want = entry.gated_group_norm_xla(v, qkvz, w, group=128, eps=1e-6,
                                      gate_first=False,
                                      offset=2 * key + val)
    assert rel(out, want) < 1e-2
    after = sites()
    assert after["conv_kernel_sites"] == before.get("conv_kernel_sites",
                                                    0) + 1
    assert after["norm_kernel_sites"] == before.get("norm_kernel_sites",
                                                    0) + 1
    assert after["conv_xla_sites"] == before.get("conv_xla_sites", 0)
    assert after["norm_xla_sites"] == before.get("norm_xla_sites", 0)


@pytest.mark.parametrize("what,kw", [
    ("width no multiple of 128", dict(C=192)),
    ("offset no multiple of 128", dict(offset=64)),
    ("no row block divides S", dict(S=96)),
    ("heads of 64 under the L2 norm", dict(head=64)),
])
def test_conv_refused_shape_takes_the_xla_form(what, kw):
    S, C, offset, head = (kw.get(k, d) for k, d in (
        ("S", 64), ("C", 256), ("offset", 0), ("head", 128)))
    x, = draw(7, (1, S, offset + C + 128))
    taps, = draw(8, (4, C))
    runs = ((C, 1.0),) if "head" in kw else ((C, None),)
    before = sites()
    got, = entry.conv_act(x, taps, offset=offset, runs=runs,
                          head_width=head)
    want, = entry.conv_act_xla(x, taps, offset=offset, runs=runs,
                               head_width=head)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    after = sites()
    assert after["conv_xla_sites"] == before.get("conv_xla_sites", 0) + 1
    assert after["conv_kernel_sites"] == before.get("conv_kernel_sites", 0)


def test_a_wide_group_is_its_own_column_block_of_fewer_rows():
    """The plan at granite-4.0-h-micro's shape (one group of 4,096 at 16,384
    tokens) against Nemotron's (8 groups of 512), which stays as it was."""
    assert kernels.norm_block(4096, 0, 512) == 512
    assert kernels.norm_row_block(16384, 512) == 1024
    assert kernels.norm_block(4096, 0, 4096) == 4096
    assert kernels.norm_row_block(16384, 4096) == 128
    assert kernels.norm_block(2048, 2048, 1024) == 1024
    assert kernels.norm_row_block(16384, 1024) == 512
    assert kernels.norm_takes(16384, 4096, 8512, 0, 4096)
    assert kernels.norm_takes(16384, 4096, 10304, 0, 512)
    plan = kernels.NormPlan(1, 16384, 4096, 8512, 0, 4096, True, 1e-5,
                            128, 4096)
    assert plan.slab == 512
    assert kernels.NormPlan(1, 64, 640, 1280, 640, 640, False, 1e-5, 64,
                            640).slab == 128
    # a group of three vreg columns inside a column block: one pass, whole
    assert kernels.NormPlan(1, 64, 384, 384, 0, 384, True, 1e-5, 64,
                            384).slab == 384
    # a gate at an offset that is no multiple of the wide group
    assert not kernels.norm_takes(16384, 4096, 8512, 512, 4096)


@pytest.mark.parametrize("what,kw", [
    ("group no multiple of 128", dict(group=64)),
    ("gate at an offset no block divides", dict(offset=64)),
    ("no row block divides S", dict(S=96)),
])
def test_norm_refused_shape_takes_the_xla_form(what, kw):
    S, group, offset = (kw.get(k, d) for k, d in (
        ("S", 64), ("group", 128), ("offset", 0)))
    y, z = draw(9, (1, S, 256), (1, S, 256 + offset))
    w, = draw(10, (256,))
    args = dict(group=group, eps=1e-5, gate_first=True, offset=offset)
    before = sites()
    got = entry.gated_group_norm(y, z, w, **args)
    want = entry.gated_group_norm_xla(y, z, w, **args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    after = sites()
    assert after["norm_xla_sites"] == before.get("norm_xla_sites", 0) + 1
    assert after["norm_kernel_sites"] == before.get("norm_kernel_sites", 0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_heads_zero_padded_to_whole_tiles_are_the_heads_own_stages(dtype):
    """Heads of 96 (q, k) and 192 (v, z, o) laid out zero-padded to 128 and
    256 lanes: the convolution's L2 norm over 128 lanes is the norm over the
    head's 96 (zeros add nothing to the sum of squares) and
    ``tile_group_norm`` over groups of 256 is the RMS norm over the head's
    192 (eps and weight rescaled), values and every gradient against the
    XLA forms at the heads' OWN widths; the padded lanes leave as zeros.
    Both calls take the kernels."""
    dt = jnp.dtype(dtype)
    B, S, H = 1, 64, 2
    pad = lambda t, d, p: jnp.pad(t.reshape(*t.shape[:-1], H, d), (  # noqa: E731
        (0, 0),) * (t.ndim) + ((0, p - d),)).reshape(*t.shape[:-1], H * p)
    cut = lambda t, d, p: t.reshape(*t.shape[:-1], H, p)[..., :d].reshape(  # noqa: E731
        *t.shape[:-1], H * d)
    x, z, o = draw(11, (B, S, H * 96), (B, S, H * 192), (B, S, H * 192),
                   dtype=dt)
    taps, w = draw(12, (4, H * 96), (192,))
    before = sites()

    def padded(x, taps, o, z, w):
        q, = entry.conv_act(pad(x, 96, 128), pad(taps, 96, 128),
                            runs=((H * 128, 0.5),), head_width=128)
        n = entry.tile_group_norm(pad(o, 192, 256), pad(z, 192, 256), w,
                                  group=256, width=192, eps=1e-6)
        return q, n

    def own(x, taps, o, z, w):
        q, = entry.conv_act_xla(x, taps, runs=((H * 96, 0.5),),
                                head_width=96)
        n = entry.gated_group_norm_xla(o, z, w, group=192, eps=1e-6,
                                       gate_first=False)
        return q, n

    q, n = padded(x, taps, o, z, w)
    after = sites()
    assert after["conv_kernel_sites"] == before.get("conv_kernel_sites",
                                                    0) + 1
    assert after["norm_kernel_sites"] == before.get("norm_kernel_sites",
                                                    0) + 1
    want_q, want_n = own(x, taps, o, z, w)
    assert not np.any(np.asarray(q.reshape(B, S, H, 128)[..., 96:], F32))
    assert not np.any(np.asarray(n.reshape(B, S, H, 256)[..., 192:], F32))
    assert rel(cut(q, 96, 128), want_q) < LIMIT[dtype]
    assert rel(cut(n, 192, 256), want_n) < LIMIT[dtype]

    def loss(fn, shrink):
        def total(*a):
            q, n = fn(*a)
            if shrink:
                q, n = cut(q, 96, 128), cut(n, 192, 256)
            return jnp.sum(jnp.sin(q.astype(F32))) + jnp.sum(
                jnp.sin(n.astype(F32)))
        return jax.jit(jax.grad(total, argnums=(0, 1, 2, 3, 4)))

    got = loss(padded, True)(x, taps, o, z, w)
    want = loss(own, False)(x, taps, o, z, w)
    for name, a, b in zip("x taps o z w".split(), got, want):
        assert rel(a, b) < 3 * LIMIT[dtype], name


def test_a_refusal_names_the_condition_that_refused():
    """``conv_refusal`` / ``norm_refusal`` are the ``*_takes`` rules in
    words (None where the kernels take the call), one condition each, and
    the entry's log line carries them."""
    ok = dict(S=8192, C=3840, total=23040, offset=3840, W=4, l2_head=128)
    assert kernels.conv_refusal(**ok) is None and kernels.conv_takes(**ok)
    for over, said in ((dict(S=8200), "row block"),
                       (dict(C=2880, offset=2880), "2880 columns at offset"),
                       (dict(W=12), "12 taps"), (dict(total=7000), "pass"),
                       (dict(l2_head=96), "96 wide")):
        why = kernels.conv_refusal(**dict(ok, **over))
        assert said in why and not kernels.conv_takes(**dict(ok, **over))
    ok = dict(S=8192, D=7680, total=23040, offset=15360, group=256)
    assert kernels.norm_refusal(**ok) is None and kernels.norm_takes(**ok)
    for over, said in ((dict(group=192), "groups of 192"),
                       (dict(offset=15360 + 128), "offset"),
                       (dict(total=20000), "pass"),
                       (dict(S=8200), "row block")):
        why = kernels.norm_refusal(**dict(ok, **over))
        assert said in why and not kernels.norm_takes(**dict(ok, **over))
