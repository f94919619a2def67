"""What the two scan kernels' forward rules share (``gated_delta.py``,
``ssd.py``): the checkpoint name their outputs carry, and the call that
writes the output alone where nothing reads the rest.

A scan's forward kernel has two forms: ``keep=False`` writes the output,
``keep=True`` also what the backward kernel reads beside the operands (every
chunk's starting state; the delta rule's ``T``). The forward rule of the
custom VJP calls the second and puts output and residuals under ``SCAN_NAME``.
Under a block's remat that decides what runs where:

- a policy that does NOT keep the name: the forward pass reads the output
  only, so the call is cut back to the ``keep=False`` kernel
  (``jax.experimental.custom_dce``: the rule below), and the recomputation
  runs the ``keep=True`` kernel — two forward calls a layer, as before the
  name existed;
- a policy that keeps it (``runtime/remat_budget.py`` where the bytes fit):
  the forward pass runs the ``keep=True`` kernel once, its three outputs
  live to the backward pass, and the recomputation holds no forward call.
"""

from jax.ad_checkpoint import checkpoint_name
from jax.experimental.custom_dce import custom_dce

# a scan kernel's output and what its forward rule writes beside it
SCAN_NAME = "scan_states"


def named_forward(forward):
    """``forward(*operands, keep=...)`` as a forward rule calls it: the
    outputs of ``keep=True`` under ``SCAN_NAME``; where only the first is
    read, the ``keep=False`` call (which returns it alone) stands in."""

    @custom_dce
    def call(*operands):
        return tuple(forward(*operands, keep=True))

    @call.def_dce
    def cut(used, *operands):
        if any(used[1:]):
            return tuple(forward(*operands, keep=True))
        return (forward(*operands, keep=False),) + (None,) * (len(used) - 1)

    def named(*operands):
        return tuple(checkpoint_name(t, SCAN_NAME) for t in call(*operands))

    return named
