"""What a rematted block keeps beside its base names: bytes against a budget.

A stack whose blocks are rematted one by one (``models/gpt2.
block_remat_policy``) keeps ``REMAT_BASE_NAMES`` whatever the memory, and of
``REMAT_CANDIDATES`` — one order for every model, dearest a byte first —
as many as fit what the chip has left. Three figures, each from what the code
can see before it compiles:

- **free bytes**: the engine's (``runtime/engine.py``: what a program of the
  device's kind may use, less ``HEADROOM_BYTES``, less what a chip holds
  across steps and the compute copy and gradients of its parameters), handed
  to the trace under ``parallel/mesh.layout_pins``. No engine round the
  model, or a device kind ``PROGRAM_HBM_BYTES`` does not know (the CPU):
  zero, and the program is the base set's.
- **the reserve**: what the program needs beside them and the kept names,
  counted from the stack's own shapes (``reserve_bytes``): every block's
  input (``layers x streams`` widths of the residual stream), the ends of
  the one block whose backward is in flight (``BLOCK_END_WIDTHS`` widths a
  stream: its input as recomputed and as normed, the cotangent that comes
  in and the one that goes out), and what the WIDEST branch of the widest
  block holds between its recomputation and the end of its backward — the
  model's ``remat_inflight_row_bytes``, put together from the counts below:
  an attention branch's q, o, their cotangents, dk and dv a query head and
  the float32 dq partials the chunked backward leaves to be summed
  (``attention_inflight``), a dense MLP's pre-activations and the
  activation's cotangent (``mlp_inflight``), a mixer's input projection and
  its cotangent (``projection_inflight``), the expert layer's slab
  (``moe/dropless.inflight_row_bytes``). A block's branches follow one
  another in its backward, so the widest counts, not their sum; the loss
  head's chunk is gone before the first block's backward starts and is
  narrower than any of them. Calibrated against the compiled peaks of the
  seven cells that call with figures (PERF.md Findings PR 64: the count is
  1.04 ... 2.1 x what the compiler's peak leaves unexplained — 6.9 x on
  Xing4.0, whose four streams' ends it counts — and never under).
- **a name's bytes**: the rows in flight times the bytes a row the layers
  that carry the name hold under it (a model's ``remat_row_bytes``).

A name is kept for all its layers or none (the blocks of a stack share one
policy object). No process state enters: a cell lowers to the same text in
every process. The gauges ``remat/kept_names``, ``remat/kept_mb``,
``remat/budget_mb`` and ``remat/reserve_mb`` say what the last stack traced
took, ``remat/scan_states_kept`` whether the scan kernels' forward-rule
outputs (``ops/pallas/scan_residuals.py``) were among it. A stack that prunes
its attention by a learned indexer keeps one name more whatever the memory —
the selection (``selection_pin_bytes``, counted from shapes:
``remat/selection_pin_mb``) — and, where its bytes fit what the selection and
the reserve leave, the KL's gradient in the indexer's scores
(``keep_kl_grad``: ``remat/dsa_kl_grad_mb``, ``remat/dsa_kl_grad_kept``).
"""

from deepspeed_tpu.ops.pallas.scan_residuals import SCAN_NAME
from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.utils.logging import log_dist

# bytes of HBM ONE program may use, keyed by ``device.device_kind`` as JAX
# reports it (v5e: 15.75 GiB of the chip's 16, what the compiler holds a
# program's peak against)
PROGRAM_HBM_BYTES = {"TPU v5 lite": 16_911_433_728}
# left over under that once the names are kept
HEADROOM_BYTES = 1_000_000_000
# widths of the residual stream (a stream) a block's backward holds at its
# ends: the block's input as recomputed and as its norm left it, the
# cotangent that comes in and the one that goes out
BLOCK_END_WIDTHS = 4


def free_bytes(device_kind, held_bytes):
    """What the names may spend before the reserve: the device kind's
    program memory less the headroom less ``held_bytes``; 0 for a kind the
    table does not know."""
    return max(0, PROGRAM_HBM_BYTES.get(device_kind, 0) - HEADROOM_BYTES
               - int(held_bytes))


def attention_inflight(q_cols, v_cols, kv_cols, itemsize, dq_slab_rows=0.0,
                       gated=False):
    """Bytes a row an attention branch's backward holds: q, its cotangent
    and dk a query head (``q_cols`` = query heads x the q.k width each), o,
    its cotangent and dv a query head (``v_cols`` = query heads x the value
    width; a second o and cotangent where an output gate multiplies it), k
    and v as stored (``kv_cols``), and the float32 dq partials of
    ``dq_slab_rows`` rows a sequence row
    (``ops/pallas/flash_attention.bwd_dq_slab_rows``)."""
    return itemsize * (3 * q_cols + (5 if gated else 3) * v_cols + kv_cols) \
        + int(4 * dq_slab_rows * q_cols)


def mlp_inflight(d_ff, itemsize, gated=True):
    """Bytes a row a dense MLP's backward holds: its pre-activations (two
    where ``gated``) and the activation's cotangent."""
    return itemsize * d_ff * (3 if gated else 2)


def projection_inflight(cols, itemsize):
    """Bytes a row a mixer's input projection ``cols`` wide and its
    cotangent take."""
    return 2 * itemsize * cols


def reserve_bytes(rows, hidden, layers, itemsize, inflight_row_bytes,
                  streams=1):
    """What the program holds at its peak beside the engine's and the kept
    names: a block input a layer and the ends of the block in flight
    (``streams`` residual streams wide), and ``inflight_row_bytes`` a row of
    that block's widest branch."""
    return rows * (hidden * itemsize * streams * (layers + BLOCK_END_WIDTHS)
                   + inflight_row_bytes)


def name_bytes(rows, row_bytes):
    """{name: bytes kept under it} of ``rows`` rows in flight and
    ``row_bytes`` {name: bytes a row, summed over the layers that carry
    it}."""
    return {name: rows * b for name, b in row_bytes.items()}


def selection_pin_bytes(batch, seq, layers, tile=512):
    """Bytes a stack whose layers prune their attention by a learned indexer
    keeps under the kernels' ``SELECTION_NAME`` whatever the memory, beside
    the base names: a layer's kept set as bits (a bit a (key, query) pair of
    the sequence padded to the kernels' tile) and two float32 / int32 rows a
    query (the kept scores' log-sum-exp, their count). 33.6 MB a layer at
    16,384 tokens. Sets ``remat/selection_pin_mb``."""
    padded = -(-seq // tile) * tile
    pinned = layers * batch * (padded * padded // 8 + 8 * padded)
    default_registry().gauge("remat/selection_pin_mb").set(pinned / 1e6)
    return pinned


def kept_names(candidates, bytes_by_name, budget):
    """The names of ``candidates``, in their order, whose bytes fit what is
    left of ``budget`` after those before them: a name that does not fit is
    passed over, a cheaper one after it may still be kept. A name no layer
    carries (0 bytes) is not listed."""
    kept = []
    for name in candidates:
        need = bytes_by_name.get(name, 0)
        if 0 < need <= budget:
            kept.append(name)
            budget -= need
    return tuple(kept)


def keep_for_stack(candidates, rows, hidden, layers, itemsize, row_bytes,
                   inflight_row_bytes, streams=1):
    """``kept_names`` for the stack being traced: the scope's free bytes
    less the stack's reserve is the budget. Sets the five gauges."""
    free = mesh_lib.pinned_remat_free_bytes()
    reserve = reserve_bytes(rows, hidden, layers, itemsize,
                            inflight_row_bytes, streams)
    budget = max(0, free - reserve)
    sizes = name_bytes(rows, row_bytes or {})
    kept = kept_names(candidates, sizes, budget)
    kept_b = sum(sizes[n] for n in kept)
    reg = default_registry()
    reg.gauge("remat/kept_names").set(len(kept))
    reg.gauge("remat/kept_mb").set(kept_b / 1e6)
    reg.gauge("remat/budget_mb").set(budget / 1e6)
    reg.gauge("remat/reserve_mb").set(reserve / 1e6)
    reg.gauge("remat/scan_states_kept").set(int(SCAN_NAME in kept))
    if free:
        log_dist(
            f"rematted blocks keep {', '.join(kept) or 'their base names only'}"
            f" ({kept_b / 1e6:.0f} MB of a budget of {budget / 1e6:.0f} MB; "
            + ", ".join(f"{n} {sizes.get(n, 0) / 1e6:.0f}" for n in candidates)
            + f" MB; {rows} rows x {layers} layers, {free / 1e6:.0f} MB free "
            f"before a reserve of {reserve / 1e6:.0f} MB: "
            f"{inflight_row_bytes} bytes a row in flight)", ranks=[0])
    return kept


def kl_grad_bytes(batch, seq, layers, itemsize, tile=512):
    """Bytes a stack whose layers prune their attention by a learned indexer
    holds under the kernels' ``KL_GRAD_NAME`` where its blocks keep it: a
    layer's KL gradient in the indexer's scores, the causal tiles alone, in
    the indexer's dtype. 277 MB a layer at 16,384 tokens in bf16."""
    from deepspeed_tpu.ops.pallas.learned_sparse_attention import \
        tiles_walked
    return layers * batch * tiles_walked(seq, tile) * tile * tile * itemsize


def learned_sparse_inflight(seq, itemsize, tile=512):
    """Bytes a row an attention branch pruned by a learned indexer holds
    beside ``attention_inflight``'s: a query's row of the float32 scores, of
    the int8 mask and of its transpose (the sequence padded to the kernels'
    tile), and its share of one layer's KL gradient as ``kl_grad_bytes``
    counts it."""
    padded = -(-seq // tile) * tile
    return 6 * padded + -(-kl_grad_bytes(1, seq, 1, itemsize, tile) // seq)


def keep_kl_grad(batch, seq, hidden, layers, itemsize, inflight_row_bytes,
                 tile=512):
    """Whether the blocks of the stack being traced keep ``KL_GRAD_NAME``:
    its bytes fit the scope's free bytes less the selection's pin less the
    stack's reserve. Free bytes of 0 (no engine, a kind the table does not
    know, a step the compiler refused once) keep nothing. Sets
    ``remat/dsa_kl_grad_mb`` and ``remat/dsa_kl_grad_kept``."""
    need = kl_grad_bytes(batch, seq, layers, itemsize, tile)
    free = mesh_lib.pinned_remat_free_bytes()
    pin = selection_pin_bytes(batch, seq, layers, tile)
    reserve = reserve_bytes(batch * seq, hidden, layers, itemsize,
                            inflight_row_bytes)
    budget = max(0, free - pin - reserve)
    kept = need <= budget
    reg = default_registry()
    reg.gauge("remat/dsa_kl_grad_mb").set(need / 1e6)
    reg.gauge("remat/dsa_kl_grad_kept").set(int(kept))
    if free:
        log_dist(
            f"rematted blocks {'keep' if kept else 'do not keep'} the KL's "
            f"gradient in the indexer's scores ({need / 1e6:.0f} MB against "
            f"a budget of {budget / 1e6:.0f} MB: {free / 1e6:.0f} MB free "
            f"less the selection's {pin / 1e6:.0f} MB less a reserve of "
            f"{reserve / 1e6:.0f} MB, {inflight_row_bytes} bytes a row in "
            f"flight; {batch} x {seq} rows x {layers} layers)", ranks=[0])
    return kept
