"""1F1B SPMD pipeline executor — the TPU-native execution of the reference's
TrainSchedule (deepspeed/runtime/pipe/schedule.py:182, engine interpreter
pipe/engine.py:1209).

The reference runs N processes, each interpreting a per-rank instruction
list and exchanging tensors over NCCL p2p. Here the whole pipeline is ONE
SPMD program under `jax.custom_vjp`:

- **forward** (`_forward_program`): GPipe fill/drain over M + S - 1 ticks;
  each tick applies the stage body and rotates activations one hop around
  the 'pipe' mesh axis with `lax.ppermute`. Nothing is saved for backward
  beyond (params, inputs) — O(1) activation memory.
- **backward**: a hand-written replay. Two tick programs:

  * **interleaved** (`interleave=True`) — the reference's even/odd 1F1B
    schedule over 2·(M + S - 1) ticks. The tick → (micro_batch, fwd|bwd)
    mapping is the closed form of `TrainSchedule._step_to_micro_batch`
    (schedule.py:220-251):

        is_fwd(t, s)  =  t ≡ s (mod 2)
        fwd µbatch    =  t//2 - s//2          (fwd(m) at t = 2m + s)
        bwd µbatch    =  t//2 - S + 1 + s//2  (bwd(m) at t = 2m + 2S - 1 - s)

    (`tests/test_pipeline_1f1b.py` asserts this closed form agrees with
    the TrainSchedule instruction stream tick-for-tick, so schedule.py is
    the executable contract, not documentation.) Each stage keeps a
    rotating buffer of its stage inputs with `num_pipe_buffers =
    min(S + 1, M)` slots — the reference's memory bound
    (schedule.py:243-247). **Constraint:** fwd/bwd ticks run in `lax.cond`
    branches selected per stage, so the stage body must not contain
    cross-device collectives — with TP/ZeRO axes active, GSPMD would place
    model/data-axis collectives inside diverging branches and the devices
    deadlock (a fundamental SPMD-pipelining constraint, not an
    implementation detail).

  * **uniform** (`interleave=False`) — fill/drain forward then drain
    backward, every device executing the identical op sequence every tick
    (invalid ticks compute on zeros and mask their writes). Auto-axis
    collectives from ZeRO/TP/SP inside the stage body stay aligned across
    devices, so this variant composes with any mesh. Same tick count and
    bubble as the interleaved schedule — 1F1B's advantage is memory, not
    bubble — but the stage-input buffer is O(M) instead of O(S).

  Default: interleaved exactly when the mesh has no non-trivial axis other
  than 'pipe'.

  A backward tick recomputes the stage forward under `jax.vjp` from the
  buffered input (rematerialization — the TPU analog of the reference's
  activation checkpointing default) and sends the input-cotangent one hop
  backwards.

Because both programs are forward-only as far as JAX autodiff is concerned
(the custom VJP *is* the backward), no collective inside them is ever
transposed — which removes the f32 upcast workarounds the autodiff GPipe
path needed around XLA-CPU's bf16 all-reduce promotion (kept only for the
two explicit result psums, gated to non-TPU backends).

Compute cost: fwd + (fwd + vjp) ≈ one extra forward per step — identical
to full-remat GPipe (what the engine paid before), but live activations
drop from O(M + S) microbatch buffers plus scan residuals to the
stage-input buffer above.
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.utils.platform import is_tpu_backend


def stack_stage_params(params, num_stages):
    """[L, ...] layer-stacked pytree → [S, L//S, ...] stage-stacked."""
    def reshape(x):
        L = x.shape[0]
        assert L % num_stages == 0, (
            f"layer count {L} not divisible by {num_stages} stages")
        return x.reshape((num_stages, L // num_stages) + x.shape[1:])
    return jax.tree_util.tree_map(reshape, params)


def unstack_stage_params(params):
    """[S, L//S, ...] → [L, ...]."""
    return jax.tree_util.tree_map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), params)


def _tick_to_micro_batch(t, stage_id, num_stages):
    """Closed form of TrainSchedule._step_to_micro_batch (see module doc).

    Works elementwise on traced values (stage_id is `lax.axis_index`).
    Returns (micro_batch_id, is_forward); the id is unclipped — callers
    mask with 0 <= id < M.
    """
    is_fwd = (t % 2) == (stage_id % 2)
    m = jnp.where(is_fwd,
                  t // 2 - stage_id // 2,
                  t // 2 - num_stages + 1 + stage_id // 2)
    return m, is_fwd


def num_pipe_buffers(num_stages, micro_batches):
    """Rotating stage-input slots needed by the 1F1B interleave: stage s
    sees fwd(m) at tick 2m+s and bwd(m) at 2m+2S-1-s, so at most S - s
    inputs are live at once (reference schedule.py:243-247)."""
    return max(2, min(num_stages + 1, micro_batches))


def _pvary(x):
    """Mark a replicated value as pipe-varying so it can seed scan carries
    that collectives/conditionals make device-varying. Nothing
    differentiates through these programs (the custom VJP is the backward),
    so the cast has no transpose cost."""
    return jax.lax.pcast(x, (mesh_lib.PIPE_AXIS,), to="varying")


def _psum_pipe(x):
    """psum over 'pipe'; upcast on CPU where XLA's AllReducePromotion pass
    crashes on bf16 all-reduce emitted from manual regions."""
    if is_tpu_backend():
        return jax.lax.psum(x, mesh_lib.PIPE_AXIS)
    return jax.lax.psum(x.astype(jnp.float32),
                        mesh_lib.PIPE_AXIS).astype(x.dtype)


def _make_forward_program(stage_fn, M, S, interleave, fwd_perm, shard,
                          param_specs):
    """Forward fill/drain tick program, shared by the training pipeline
    (as the custom-vjp primal) and `pipeline_infer` (as the executed
    InferenceSchedule): stage i computes micro m at tick t = m + i over
    M + S - 1 ticks — exactly InferenceSchedule's step→µbatch mapping
    (runtime/pipe/schedule.py:138, `micro_batch_id = step_id - stage_id`);
    the rotating activation hop (ppermute) is its 2-slot buffer."""
    @functools.partial(shard, in_specs=(param_specs, P()), out_specs=P())
    def _forward_program(sp, mb):
        local = jax.tree_util.tree_map(lambda p: p[0], sp)
        idx = jax.lax.axis_index(mesh_lib.PIPE_AXIS)
        zero_mb = jnp.zeros_like(mb[0])

        def tick(carry, t):
            recv_act, out_buf = carry
            m = t - idx                      # fill/drain: stage i runs m = t - i
            valid = (m >= 0) & (m < M)
            x = jnp.where(idx == 0, mb[jnp.clip(t, 0, M - 1)], recv_act)
            if interleave:
                # skip garbage fill/drain ticks (collective-free body)
                y = jax.lax.cond(valid, lambda xx: stage_fn(local, xx),
                                 lambda xx: jnp.zeros_like(xx), x)
            else:
                # uniform: every device runs the body every tick so any
                # auto-axis collectives inside stay aligned
                y = stage_fn(local, x)
            is_out = valid & (idx == S - 1)
            slot = jnp.clip(m, 0, M - 1)
            out_buf = jnp.where(
                is_out,
                jax.lax.dynamic_update_index_in_dim(out_buf, y, slot, 0),
                out_buf)
            recv_act = jax.lax.ppermute(y, mesh_lib.PIPE_AXIS, fwd_perm)
            return (recv_act, out_buf), None

        out_buf0 = _pvary(jnp.zeros_like(mb))
        (_, out_buf), _ = jax.lax.scan(
            tick, (_pvary(zero_mb), out_buf0), jnp.arange(M + S - 1))
        # broadcast the last stage's results to every stage so downstream
        # (loss) code is stage-agnostic
        return _psum_pipe(jnp.where(idx == S - 1, out_buf,
                                    jnp.zeros_like(out_buf)))
    return _forward_program


def _nonpipe_axes_in_param_specs(stage_params):
    """Mesh axes other than 'pipe' that appear in the stage params'
    shardings. A param sharded over a live data/model axis forces GSPMD to
    insert a collective (all-gather / reduce-scatter) inside the stage
    body, which is exactly the thing the interleaved schedule cannot
    tolerate.

    Inspects concrete-array `.sharding` (eager callers) and falls back to
    `.aval.sharding` (explicit-sharding tracers). Under plain jit in Auto
    mode tracers expose neither — that path is covered by the jaxpr scan
    in `_collective_axes_in_body` for explicit collectives; GSPMD-inserted
    ones are undetectable at trace time (documented limitation)."""
    axes = set()
    for leaf in jax.tree_util.tree_leaves(stage_params):
        spec = getattr(getattr(leaf, "sharding", None), "spec", None)
        if spec is None:
            spec = getattr(
                getattr(getattr(leaf, "aval", None), "sharding", None),
                "spec", None)
        if spec is None:
            continue
        for entry in spec:
            if entry is None:
                continue
            names = entry if isinstance(entry, tuple) else (entry,)
            axes.update(n for n in names if n != mesh_lib.PIPE_AXIS)
    return axes


def _axis_names_in_jaxpr(jaxpr, found):
    """Collect mesh-axis names referenced by collective-style primitives
    (psum/ppermute/all_gather/... carry them in 'axes'/'axis_name' params),
    recursing into sub-jaxprs (scan/cond/closed_call/shard_map bodies)."""
    for eqn in jaxpr.eqns:
        for key in ("axes", "axis_name"):
            v = eqn.params.get(key)
            if isinstance(v, str):
                found.add(v)
            elif isinstance(v, (tuple, list, frozenset, set)):
                found.update(n for n in v if isinstance(n, str))
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                _axis_names_in_jaxpr(sub, found)
            elif isinstance(v, (tuple, list)):
                for w in v:
                    subw = getattr(w, "jaxpr", w)
                    if hasattr(subw, "eqns"):
                        _axis_names_in_jaxpr(subw, found)


def _collective_axes_in_body(stage_fn, stage_params, microbatches, live):
    """Best-effort trace of the stage body looking for explicit collectives
    over live non-pipe mesh axes (ring attention's ppermute over 'seq', a
    hand-written psum over 'model', ...). Works on tracers too — the trace
    is abstract. Returns the offending axis names (empty = no proof).

    A trace failure that names a live axis (unbound axis name) is itself
    proof the body references that axis."""
    try:
        local_abs = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape[1:], p.dtype),
            stage_params)
        x_abs = jax.ShapeDtypeStruct(microbatches.shape[1:],
                                     microbatches.dtype)
        jaxpr = jax.make_jaxpr(stage_fn)(local_abs, x_abs)
        found = set()
        _axis_names_in_jaxpr(jaxpr.jaxpr, found)
        return found & live
    except Exception as e:
        # JAX reports a collective over a mesh axis traced outside its
        # binding as "unbound axis name: <axis>" — that exact failure IS
        # the proof. Any other trace failure proves nothing; stay silent
        # (the real error will resurface when the actual program traces).
        msg = str(e)
        if "unbound axis name" in msg:
            return {a for a in live if a in msg}
        return set()


def _pipeline_prologue(stage_params, microbatches, mesh, interleave,
                       stage_fn=None):
    """Shared setup for the training and inference executors: resolves the
    interleave mode (hard error on the forced-interleave + live-ZeRO/TP-spec
    hazard, warning for the maybe-collective-free case), permutations, param
    specs and the pipe-only shard_map.
    Returns None when S == 1 (callers fall back to a sequential map)."""
    S = mesh.shape[mesh_lib.PIPE_AXIS]
    if S == 1:
        return None
    others = 1
    for name, size in mesh.shape.items():
        if name != mesh_lib.PIPE_AXIS:
            others *= size
    if interleave is None:
        interleave = others == 1
    elif interleave and others > 1:
        # forced interleave on a mesh with live data/model/seq axes: any
        # GSPMD collective inside the stage body lands in diverging
        # lax.cond branches and the devices DEADLOCK (see module doc).
        # When the stage params carry ZeRO/TP specs over those axes the
        # collective is GUARANTEED (GSPMD must gather the shards to apply
        # the layer), so refuse to build a program that cannot run.
        # Otherwise (replicated params, batch-sharded elementwise body may
        # be collective-free) keep the warning.
        live = {k: v for k, v in mesh.shape.items()
                if k != mesh_lib.PIPE_AXIS and v > 1}
        spec_axes = _nonpipe_axes_in_param_specs(stage_params) & live.keys()
        if not spec_axes and stage_fn is not None:
            spec_axes = _collective_axes_in_body(
                stage_fn, stage_params, microbatches, live.keys())
        if spec_axes:
            raise ValueError(
                f"pipeline interleave=True is impossible on this mesh: the "
                f"stage params/body use live non-pipe axes "
                f"{sorted(spec_axes)} (mesh {live}), so collectives land "
                f"inside the interleaved schedule's diverging lax.cond "
                f"branches and the devices deadlock. Use interleave=False "
                f"(the uniform schedule composes with ZeRO/TP/SP) or drop "
                f"the ZeRO/TP specs from the stage params.")
        from deepspeed_tpu.utils.logging import logger
        logger.warning(
            "pipeline interleave=True forced on a mesh with non-pipe axes "
            "%s: the stage body must be collective-free or the program "
            "deadlocks; the uniform schedule composes safely", live)

    M = microbatches.shape[0]
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    param_specs = jax.tree_util.tree_map(
        lambda x: P(mesh_lib.PIPE_AXIS, *([None] * (x.ndim - 1))),
        stage_params)
    shard = functools.partial(
        jax.shard_map, mesh=mesh,
        axis_names=frozenset({mesh_lib.PIPE_AXIS}))
    return S, M, interleave, fwd_perm, param_specs, shard


def pipeline_infer(stage_fn, stage_params, microbatches, mesh,
                   interleave=None):
    """Execute the InferenceSchedule: forward-only pipelining of M
    microbatches through S stages (the role of the reference's
    _exec_schedule interpreting InferenceSchedule,
    pipe/engine.py:1209 + schedule.py:129). No backward program is built
    and nothing differentiates through this — use for eval/serving.

    Same contract as pipeline_1f1b's forward: returns the last stage's
    outputs [M, ...], replicated over 'pipe'.
    """
    setup = _pipeline_prologue(stage_params, microbatches, mesh, interleave,
                               stage_fn=stage_fn)
    if setup is None:
        squeezed = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        return jax.lax.map(lambda x: stage_fn(squeezed, x), microbatches)
    S, M, interleave, fwd_perm, param_specs, shard = setup
    program = _make_forward_program(stage_fn, M, S, interleave, fwd_perm,
                                    shard, param_specs)
    return program(stage_params, microbatches)


def pipeline_1f1b(stage_fn, stage_params, microbatches, mesh,
                  interleave=None):
    """Run M microbatches through S = mesh.shape['pipe'] stages; returns the
    last stage's outputs [M, ...] (replicated over 'pipe').

    stage_fn(stage_local_params, x) -> y with y.shape == x.shape.
    stage_params: pytree, every leaf with leading stage dim S.
    microbatches: [M, mb, ...] activations entering stage 0.
    interleave: True → reference 1F1B interleaved ticks (stage body must be
      collective-free, see module doc); False → uniform ticks (composes
      with ZeRO/TP/SP); None → auto (interleave iff 'pipe' is the only
      non-trivial mesh axis).

    Differentiable: gradients flow to both stage_params and microbatches
    through the hand-written backward program.

    Only the 'pipe' axis is shard_mapped — data/seq/model stay in GSPMD
    auto mode, so ZeRO/TP/SP shardings compose untouched.
    """
    setup = _pipeline_prologue(stage_params, microbatches, mesh, interleave,
                               stage_fn=stage_fn)
    if setup is None:
        squeezed = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        return jax.lax.map(lambda x: stage_fn(squeezed, x), microbatches)
    S, M, interleave, fwd_perm, param_specs, shard = setup
    NB = num_pipe_buffers(S, M) if interleave else M
    bwd_perm = [((i + 1) % S, i) for i in range(S)]

    def local_params(params_sharded):
        # [1, ...] per-device leaf -> drop the stage dim
        return jax.tree_util.tree_map(lambda p: p[0], params_sharded)

    # ---- forward: GPipe fill/drain, nothing saved ------------------------
    _forward_program = _make_forward_program(stage_fn, M, S, interleave,
                                             fwd_perm, shard, param_specs)

    # ---- backward: even/odd 1F1B replay (interleaved) --------------------
    dparam_specs = param_specs

    @functools.partial(shard, in_specs=(param_specs, P(), P()),
                       out_specs=(dparam_specs, P()))
    def _backward_interleaved(sp, mb, douts):
        local = local_params(sp)
        idx = jax.lax.axis_index(mesh_lib.PIPE_AXIS)
        zero_mb = jnp.zeros_like(mb[0])

        def tick(carry, t):
            recv_act, recv_grad, act_buf, dparams, dmb = carry
            m, is_fwd = _tick_to_micro_batch(t, idx, S)
            valid = (m >= 0) & (m < M)
            mc = jnp.clip(m, 0, M - 1)
            slot = mc % NB

            def do_fwd(c):
                _, _, act_buf, dparams, dmb = c
                x = jnp.where(idx == 0, mb[mc], recv_act)
                act_buf = jax.lax.dynamic_update_index_in_dim(
                    act_buf, x, slot, 0)
                y = stage_fn(local, x)
                return act_buf, dparams, dmb, y, jnp.zeros_like(x)

            def do_bwd(c):
                _, _, act_buf, dparams, dmb = c
                x = jax.lax.dynamic_index_in_dim(act_buf, slot, 0,
                                                 keepdims=False)
                g = jnp.where(idx == S - 1, douts[mc], recv_grad)
                _, vjp_fn = jax.vjp(stage_fn, local, x)
                dp, dx = vjp_fn(g)
                dparams = jax.tree_util.tree_map(jnp.add, dparams, dp)
                dmb_upd = jax.lax.dynamic_update_index_in_dim(dmb, dx, mc, 0)
                dmb = jnp.where(idx == 0, dmb_upd, dmb)
                return act_buf, dparams, dmb, jnp.zeros_like(x), dx

            def noop(c):
                _, _, act_buf, dparams, dmb = c
                z = _pvary(jnp.zeros_like(zero_mb))
                return act_buf, dparams, dmb, z, z

            act_buf, dparams, dmb, send_act, send_grad = jax.lax.cond(
                valid & is_fwd, do_fwd,
                lambda c: jax.lax.cond(valid, do_bwd, noop, c), carry)
            recv_act = jax.lax.ppermute(send_act, mesh_lib.PIPE_AXIS,
                                        fwd_perm)
            recv_grad = jax.lax.ppermute(send_grad, mesh_lib.PIPE_AXIS,
                                         bwd_perm)
            return (recv_act, recv_grad, act_buf, dparams, dmb), None

        carry0 = (
            _pvary(zero_mb),                            # recv_act
            _pvary(zero_mb),                            # recv_grad
            _pvary(jnp.zeros((NB,) + mb.shape[1:], mb.dtype)),  # act_buf
            jax.tree_util.tree_map(jnp.zeros_like, local),
            _pvary(jnp.zeros_like(mb)),                 # dmb
        )
        (_, _, _, dparams, dmb), _ = jax.lax.scan(
            tick, carry0, jnp.arange(2 * (M + S - 1)))
        # dmb lives on stage 0 only; replicate. dparams are per-stage and
        # re-stack over the pipe axis via the out_spec.
        dmb = _psum_pipe(dmb)
        dparams = jax.tree_util.tree_map(lambda g: g[None], dparams)
        return dparams, dmb

    # ---- backward: uniform ticks (composes with ZeRO/TP/SP) --------------

    @functools.partial(shard, in_specs=(param_specs, P(), P()),
                       out_specs=(dparam_specs, P()))
    def _backward_uniform(sp, mb, douts):
        local = local_params(sp)
        idx = jax.lax.axis_index(mesh_lib.PIPE_AXIS)
        zero_mb = jnp.zeros_like(mb[0])

        def fwd_tick(carry, t):
            recv_act, act_buf = carry
            m = t - idx
            valid = (m >= 0) & (m < M)
            mc = jnp.clip(m, 0, M - 1)
            x = jnp.where(idx == 0, mb[jnp.clip(t, 0, M - 1)], recv_act)
            act_buf = jnp.where(
                valid,
                jax.lax.dynamic_update_index_in_dim(act_buf, x, mc, 0),
                act_buf)
            y = stage_fn(local, x)
            recv_act = jax.lax.ppermute(y, mesh_lib.PIPE_AXIS, fwd_perm)
            return (recv_act, act_buf), None

        (_, act_buf), _ = jax.lax.scan(
            fwd_tick,
            (_pvary(zero_mb),
             _pvary(jnp.zeros((M,) + mb.shape[1:], mb.dtype))),
            jnp.arange(M + S - 1))

        def bwd_tick(carry, u):
            recv_grad, dparams, dmb = carry
            # reverse drain: stage i does bwd of m = u - (S - 1 - i)
            m = u - (S - 1 - idx)
            valid = (m >= 0) & (m < M)
            mc = jnp.clip(m, 0, M - 1)
            x = jax.lax.dynamic_index_in_dim(act_buf, mc, 0, keepdims=False)
            g = jnp.where(idx == S - 1, douts[mc], recv_grad)
            g = jnp.where(valid, g, jnp.zeros_like(g))
            _, vjp_fn = jax.vjp(stage_fn, local, x)
            dp, dx = vjp_fn(g)
            # garbage ticks ran the vjp (to keep collectives aligned) but
            # must contribute exactly zero; the zeroed cotangent makes dp/dx
            # zero by linearity ONLY if the stale buffer input produced
            # finite intermediates (0×Inf = NaN), so mask explicitly
            dp = jax.tree_util.tree_map(
                lambda a: jnp.where(valid, a, jnp.zeros_like(a)), dp)
            dx = jnp.where(valid, dx, jnp.zeros_like(dx))
            dparams = jax.tree_util.tree_map(jnp.add, dparams, dp)
            dmb_upd = jax.lax.dynamic_update_index_in_dim(dmb, dx, mc, 0)
            dmb = jnp.where((idx == 0) & valid, dmb_upd, dmb)
            recv_grad = jax.lax.ppermute(dx, mesh_lib.PIPE_AXIS, bwd_perm)
            return (recv_grad, dparams, dmb), None

        carry0 = (
            _pvary(zero_mb),
            jax.tree_util.tree_map(jnp.zeros_like, local),
            _pvary(jnp.zeros_like(mb)),
        )
        (_, dparams, dmb), _ = jax.lax.scan(
            bwd_tick, carry0, jnp.arange(M + S - 1))
        dmb = _psum_pipe(dmb)
        dparams = jax.tree_util.tree_map(lambda g: g[None], dparams)
        return dparams, dmb

    _backward_program = _backward_interleaved if interleave \
        else _backward_uniform

    @jax.custom_vjp
    def run(sp, mb):
        return _forward_program(sp, mb)

    def run_fwd(sp, mb):
        return _forward_program(sp, mb), (sp, mb)

    def run_bwd(res, douts):
        sp, mb = res
        return _backward_program(sp, mb, douts)

    run.defvjp(run_fwd, run_bwd)
    return run(stage_params, microbatches)
