"""Static no-forced-sync guard (ISSUE 4 satellite): the telemetry spans
are honest only if nothing in a hot path forces a device sync per step.
This test pins that property by grepping the hot-path code for host
readbacks — ``float(...)`` / ``.item(...)`` / ``np.asarray(...)`` /
``jax.device_get`` / ``block_until_ready`` — and failing on any
occurrence that is not explicitly annotated ``# sync-ok: <reason>``.

The annotation is the point: every deliberate readback (the serving
scheduler consuming sampled tokens, the steps_per_print boundary fence,
the config-gated trace-window close) is visible and justified in
source, and a NEW unannotated one — the easy way to silently serialize
dispatch against execution — fails CI instead of landing.

Scope (the per-step hot paths):
- ``deepspeed_tpu/parallel/*.py`` (overlap buckets,
  mesh/attention helpers traced into train steps),
- ``deepspeed_tpu/serving/*.py`` (the continuous-batching scheduler,
  including its watchdog hooks; ISSUE 9 grows this glob's coverage to
  the speculative drafters ``drafter.py`` — the ngram drafter must
  stay pure-host and the model drafter's proposal readback is the
  scheduler's existing sampled-token fence — and the prefix-index/COW
  admission path in ``paged_cache.py``, whose page-content probes are
  deliberate host verification at admission, not per-tick syncs),
- ``deepspeed_tpu/telemetry/*.py`` (recording must never sync — ISSUE
  6 extends this to the flight recorder ``recorder.py``, the anomaly
  watchdog ``anomaly.py`` and the dump viewer ``view.py``: rule
  evaluation and dumping consume host scalars their callers already
  read at existing fences),
- ``deepspeed_tpu/runtime/swap_tensor/*.py`` (PR 5: the pipelined swap
  schedules run on the per-step path; their d2h parks and staging-slot
  fences are deliberate and annotated),
- the train-fn builders + per-step methods of ``runtime/engine.py``,
  including the NVMe swap-schedule methods
  (``_train_batch_instrumented`` is excluded: it is the
  wall_clock_breakdown MEASUREMENT mode, whose per-phase fences are
  the documented price of turning that flag on).
"""

import inspect
import pathlib
import re
import textwrap

import deepspeed_tpu

PKG = pathlib.Path(deepspeed_tpu.__file__).parent

FORBIDDEN = re.compile(
    r"(?<![\w.])float\("        # device scalar -> host float
    r"|\.item\("                # torch/np-style scalar readback
    r"|(?<!j)np\.asarray\("     # device array -> host np (jnp.asarray ok)
    r"|jax\.device_get\("
    r"|(?<![\w.])device_get\("
    r"|block_until_ready")

ALLOW = "sync-ok"

HOT_GLOBS = ("parallel/*.py", "serving/*.py", "telemetry/*.py",
             "runtime/swap_tensor/*.py",
             # ISSUE 7: the elastic snapshot layer runs at step
             # boundaries — staging copies and swap-file reads are
             # deliberate host work, device readbacks must be annotated
             "runtime/elastic/*.py")

# engine units scanned via inspect (robust to line moves)
HOT_ENGINE_METHODS = (
    "train_batch", "forward", "backward", "step",
    "_build_jit_fns", "_build_overlap_train_fn",
    "_build_compressed_train_fn",
    "_build_sparse_train_fn", "_local_grad_accumulator",
    "_apply_grads", "_telemetry_step", "_telemetry_fold",
    "_telemetry_mfu", "_telemetry_memory_gauges", "_telemetry_export",
    # PR 5: the NVMe swap-schedule methods (park/unpark run per step;
    # the swapper's own d2h/fences live in runtime/swap_tensor/ above)
    "_ensure_params_resident", "_park_params", "_param_swap_order",
    "_make_param_swapper",
    # ISSUE 7: the elastic snapshot hook runs at every step boundary —
    # its stall accounting must stay host-timer-only (the snapshot
    # staging d2h lives in runtime/elastic/snapshot.py above)
    "_elastic_step", "_elastic_commit", "_begin_snapshot",
    "_snapshot_trees", "_make_snapshotter", "_preempt_finalize",
    "_preempt_agreed",
)


def _statements(source):
    """Group physical lines into logical statements (paren depth +
    backslash continuations) so an allow-comment on ANY line of a
    multiline statement covers exactly THAT statement — a blanket
    neighbouring-line whitelist would let an unannotated readback ride
    next to an annotated one. Depth counting is naive about brackets
    inside string literals; the scanned modules keep them balanced (the
    self-test below pins the grouping behaviour)."""
    lines = source.splitlines()
    stmts, cur, start, depth, cont = [], [], 0, 0, False
    for i, line in enumerate(lines):
        if not cur:
            start = i
        cur.append(line)
        code = line.split("#", 1)[0]
        depth += sum(code.count(c) for c in "([{") \
            - sum(code.count(c) for c in ")]}")
        cont = code.rstrip().endswith("\\")
        if depth <= 0 and not cont:
            stmts.append((start, cur))
            cur, depth = [], 0
    if cur:
        stmts.append((start, cur))
    return stmts


def _check(name, source):
    bad = []
    for start, stmt in _statements(source):
        code = "\n".join(l.split("#", 1)[0] for l in stmt)
        if FORBIDDEN.search(code) and not any(ALLOW in l for l in stmt):
            bad.append(f"{name}:{start + 1}: {stmt[0].strip()}")
    return bad


def test_hot_path_modules_have_no_unannotated_syncs():
    bad = []
    for pattern in HOT_GLOBS:
        for path in sorted(PKG.glob(pattern)):
            bad += _check(str(path.relative_to(PKG.parent)),
                          path.read_text())
    assert not bad, (
        "unannotated host readback(s) in hot-path modules — either hoist "
        "them out of the per-step path or annotate '# sync-ok: <reason>' "
        "with a justification:\n" + "\n".join(bad))


def test_engine_train_paths_have_no_unannotated_syncs():
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    bad = []
    for meth in HOT_ENGINE_METHODS:
        fn = inspect.unwrap(getattr(DeepSpeedEngine, meth))
        src = textwrap.dedent(inspect.getsource(fn))
        bad += _check(f"DeepSpeedEngine.{meth}", src)
    assert not bad, (
        "unannotated host readback(s) in engine per-step paths:\n"
        + "\n".join(bad))


def test_guard_regex_catches_the_patterns():
    """The guard itself must keep teeth: each forbidden form is caught,
    the allowed forms are not."""
    assert _check("x", "v = float(loss)\n")
    assert _check("x", "v = loss.item()\n")
    assert _check("x", "v = np.asarray(dev_arr)\n")
    assert _check("x", "v = jax.device_get(x)\n")
    assert _check("x", "jax.block_until_ready(x)\n")
    assert not _check("x", "v = jnp.asarray(host)\n")
    assert not _check("x", "v = np.float32(1.0)\n")
    assert not _check("x", "x: float = 0.0\n")
    assert not _check("x", "v = float(loss)  # sync-ok: boundary fence\n")
    # annotation on the continuation line covers a multiline statement
    assert not _check("x", "v = np.asarray(\n    a)  # sync-ok: host\n")
    # …but covers ONLY that statement: an unannotated readback on the
    # next physical line must still fail (the adjacency-whitelist hole)
    assert _check("x", "a = 1  # sync-ok: x\nv = float(dev)\n")
    assert _check("x", "v = np.asarray(\n    a)  # sync-ok: host\n"
                       "w = jax.device_get(b)\n")
