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
- **the reserve**: what the program needs beside them and the kept names —
  every block's input, the base names and the working set of the one block
  whose backward is in flight with the head's chunk — as ``rows x hidden x
  itemsize x (layers x streams + RESERVE_BLOCK_WIDTHS)``.
- **a name's bytes**: the rows in flight times the bytes a row the layers
  that carry the name hold under it (a model's ``remat_row_bytes``).

A name is kept for all its layers or none (the blocks of a stack share one
policy object). No process state enters: a cell lowers to the same text in
every process. The gauges ``remat/kept_names``, ``remat/kept_mb`` and
``remat/budget_mb`` say what the last stack traced took.
"""

from deepspeed_tpu.parallel import mesh as mesh_lib
from deepspeed_tpu.telemetry.registry import default_registry
from deepspeed_tpu.utils.logging import log_dist

# bytes of HBM ONE program may use, keyed by ``device.device_kind`` as JAX
# reports it (v5e: 15.75 GiB of the chip's 16, what the compiler holds a
# program's peak against)
PROGRAM_HBM_BYTES = {"TPU v5 lite": 16_911_433_728}
# left over under that once the names are kept
HEADROOM_BYTES = 1_000_000_000
# block-widths (rows x hidden x itemsize) a program holds at its peak beside
# the engine's bytes, one block input a layer and what its blocks keep:
# calibrated on the compiled peaks of the eight cells that remat block by
# block, names kept (PERF.md Findings PR 61: 3.4 ... 46.1, the worst covered;
# a block of attention heads four times the hidden size with float32 dq
# slabs, or a layer scan's stacked copies, is what it has to hold)
RESERVE_BLOCK_WIDTHS = 47


def free_bytes(device_kind, held_bytes):
    """What the names may spend before the reserve: the device kind's
    program memory less the headroom less ``held_bytes``; 0 for a kind the
    table does not know."""
    return max(0, PROGRAM_HBM_BYTES.get(device_kind, 0) - HEADROOM_BYTES
               - int(held_bytes))


def reserve_bytes(rows, hidden, layers, itemsize, streams=1):
    """What the base program holds at its peak beside the engine's: a block
    input a layer (``streams`` residual streams wide) and
    ``RESERVE_BLOCK_WIDTHS`` block-widths more."""
    return rows * hidden * itemsize * (layers * streams
                                       + RESERVE_BLOCK_WIDTHS)


def name_bytes(rows, row_bytes):
    """{name: bytes kept under it} of ``rows`` rows in flight and
    ``row_bytes`` {name: bytes a row, summed over the layers that carry
    it}."""
    return {name: rows * b for name, b in row_bytes.items()}


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
                   streams=1):
    """``kept_names`` for the stack being traced: the scope's free bytes
    less the stack's reserve is the budget. Sets the three gauges."""
    free = mesh_lib.pinned_remat_free_bytes()
    budget = max(0, free - reserve_bytes(rows, hidden, layers, itemsize,
                                         streams))
    sizes = name_bytes(rows, row_bytes or {})
    kept = kept_names(candidates, sizes, budget)
    kept_b = sum(sizes[n] for n in kept)
    reg = default_registry()
    reg.gauge("remat/kept_names").set(len(kept))
    reg.gauge("remat/kept_mb").set(kept_b / 1e6)
    reg.gauge("remat/budget_mb").set(budget / 1e6)
    if free:
        log_dist(
            f"rematted blocks keep {', '.join(kept) or 'their base names only'}"
            f" ({kept_b / 1e6:.0f} MB of a budget of {budget / 1e6:.0f} MB; "
            + ", ".join(f"{n} {sizes.get(n, 0) / 1e6:.0f}" for n in candidates)
            + f" MB; {rows} rows x {layers} layers, {free / 1e6:.0f} MB free "
            "before the reserve)", ranks=[0])
    return kept
