"""FLOPS profiler — rebuild of
deepspeed/profiling/flops_profiler/profiler.py:11.

The reference monkey-patches torch.nn.functional to count MACs per module.
On TPU the compiler already knows: we ask XLA for the **compiled HLO cost
analysis** of the train step (flops, bytes accessed) — exact, not estimated,
and it includes fusion effects. Per-module breakdown comes from a jaxpr walk
with flax module path annotations.
"""

import jax
import numpy as np

from deepspeed_tpu.utils.logging import logger


# per-chip dense bf16 peak FLOPS keyed by ``device.device_kind`` exactly
# as JAX reports it (Google Cloud TPU documentation, per-chip figures) —
# the denominator of the engine's ``train/mfu`` gauge.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,   # v6e
}


def flops_of_jitted(fn, *args, **kwargs):
    """Total flops of `fn(*args)` per XLA's cost analysis."""
    lowered = jax.jit(fn).lower(*args, **kwargs)
    compiled = lowered.compile()
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0)), cost
    except Exception:
        return 0.0, {}


def compiled_step_flops(jitted, *args):
    """Flops of an ALREADY-jitted callable (one exposing ``.lower``)
    per XLA's compiled cost analysis. After the first real call this is
    a compile-cache hit — which is how the engine prices its MFU gauge
    without recompiling any train path."""
    try:
        compiled = jitted.lower(*args).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        return float(cost.get("flops", 0.0))
    except Exception as e:
        logger.warning(f"cost analysis unavailable: {e}")
        return 0.0


def params_count(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


class FlopsProfiler:
    """Engine-integrated profiler (reference integration engine.py:1012-1057):
    at `profile_step` it measures the train step's exact flops + wall time
    and logs flops/s and parameter count."""

    def __init__(self, engine=None):
        self.engine = engine
        self.profiled = False
        self.last_profile = None

    def maybe_profile(self, batch):
        eng = self.engine
        cfg = eng._config.flops_profiler_config
        if self.profiled or eng.global_steps < cfg.profile_step:
            return
        self.profiled = True
        self.profile_step(batch)

    def profile_step(self, batch):
        eng = self.engine
        state = eng.state
        rng = jax.random.PRNGKey(0)
        flops, cost = self._measure(state, batch, rng)
        n_params = params_count(state.params)
        self.last_profile = {
            "flops_per_step": flops,
            "params": n_params,
            "cost_analysis": dict(cost) if cost else {},
        }
        logger.info(f"[flops_profiler] params={n_params/1e6:.2f}M "
                    f"flops/step={flops/1e9:.2f} GFLOPs")
        cfg = eng._config.flops_profiler_config
        if getattr(cfg, "detailed", False):
            table = module_breakdown(
                eng.module, eng._model_inputs(batch),
                depth=getattr(cfg, "module_depth", 2))
            if table:
                self.last_profile["module_breakdown"] = table
                logger.info("\n" + table)
        return self.last_profile

    def _measure(self, state, batch, rng):
        eng = self.engine
        lowered = eng._jit_train_batch.lower(state, batch, rng)
        compiled = lowered.compile()
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            return float(cost.get("flops", 0.0)), cost
        except Exception:
            return 0.0, {}


def module_breakdown(model, example_input, depth=2, rng=None):
    """Per-module flops/params table (the reference's annotated model tree,
    profiler.py:print_model_profile) via flax tabulate over the module
    hierarchy; depth mirrors the `module_depth` config knob."""
    try:
        import flax.linen as nn
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        depth = None if depth is None or depth < 0 else int(depth)
        tab = nn.tabulate(model, rng, compute_flops=True, depth=depth)
        return tab(example_input)
    except Exception as e:  # tabulate needs a traceable example input
        logger.warning(f"module breakdown unavailable: {e}")
        return ""


def get_model_profile(model, input_shape, rng=None, detailed=False):
    """Standalone entry mirroring the reference's get_model_profile: returns
    (flops, macs_estimate, params) for a flax model's forward pass; with
    ``detailed`` also logs the per-module table."""
    import jax.numpy as jnp
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    x = jnp.zeros(input_shape, jnp.int32)
    variables = model.init(rng, x)
    params = variables.get("params", variables)

    def fwd(p, xx):
        return model.apply({"params": p}, xx)

    flops, cost = flops_of_jitted(fwd, params, x)
    if detailed:
        table = module_breakdown(model, x)
        if table:
            logger.info("\n" + table)
    return flops, flops / 2.0, params_count(params)
