"""The seam between the paged-serving skeleton and a family's layer math.

``serving/adapters.PagedServingAdapter`` owns the KV pool; a family is
the ``serving_*`` functions of its ``models/*_inference.py``; the table
``serving.FAMILIES`` is the only place a family is named. Nothing here
compiles or runs a model: every program is traced over abstract params
(``jax.eval_shape``), so the file costs seconds.
"""

import os
import subprocess
import sys
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu.serving as serving
from deepspeed_tpu.serving.adapters import PagedServingAdapter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# sizes chosen so that no weight, activation or position array has a pool
# array's shape or the page table's: 11 blocks, 5 pages a slot, 3 slots
SLOTS, PAGE, MAXP, NBLOCKS = 3, 16, 5, 11
KINDS = ("tick", "verify", "prefill", "prefill_suffix")
BUILDERS = ("cache_spec_from_config", "build_engine", "build_router",
            "build_transport_node")


def _gpt2():
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=128,
                     n_layer=2, n_head=4, dtype=jnp.float32,
                     param_dtype=jnp.float32, scan_layers=True)
    init = GPT2LMHeadModel(cfg).init
    return cfg, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0),
                     np.zeros((1, 8), np.int32))["params"])


def _llama():
    from deepspeed_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from deepspeed_tpu.models.llama_inference import \
        convert_llama_serving_params
    cfg = LlamaConfig(vocab_size=256, hidden_size=128, n_layers=2,
                      n_heads=4, n_kv_heads=2, intermediate_size=256,
                      max_seq_len=128, dtype=jnp.float32,
                      param_dtype=jnp.float32)
    init = LlamaForCausalLM(cfg).init
    return cfg, jax.eval_shape(
        lambda: convert_llama_serving_params(
            init(jax.random.PRNGKey(0),
                 np.zeros((1, 8), np.int32))["params"], cfg))


# a tiny (config, ABSTRACT params) per family of the table — a family
# added to serving.FAMILIES adds its entry here (the first test says so)
TINY = {"gpt2": _gpt2, "llama": _llama}
FAMILY_NAMES = sorted(serving.FAMILIES)


def test_every_family_of_the_table_is_covered_here():
    assert set(TINY) == set(serving.FAMILIES)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


class _Traced:
    """One family at one cache width: the four program kinds traced once
    over abstract params, with every call into the layer math recorded."""

    def __init__(self, family, kv_bits):
        cfg, params = TINY[family]()
        spec = serving.cache_spec_from_config(
            cfg, family, None, slots=SLOTS, page_size=PAGE,
            max_pages_per_slot=MAXP, num_blocks=NBLOCKS,
            kv_cache_bits=kv_bits)
        math = serving.FAMILIES[family].math()
        self.calls = {}          # function name -> [leaf shapes per call]

        def spy(name, fn):
            def wrapped(*args, **kw):
                self.calls.setdefault(name, []).append(
                    [tuple(x.shape) for x in
                     jax.tree_util.tree_leaves((args[1:], kw))
                     if hasattr(x, "shape")])
                return fn(*args, **kw)
            return wrapped

        self.functions = sorted(n for n in vars(math)
                                if n.startswith("serving_"))
        spied = types.SimpleNamespace(
            **{n: spy(n, getattr(math, n)) for n in self.functions})
        adapter_cls = type("Spied", (PagedServingAdapter,),
                           {"math": classmethod(lambda cls: spied)})
        self.adapter = a = adapter_cls(cfg, params, spec)
        self.pool = jax.eval_shape(lambda: a.make_cache().pool)
        i32 = jnp.int32
        B = SLOTS
        pt = _sds((B, MAXP), i32)
        self.pool_shapes = {tuple(x.shape) for x in self.pool}
        self.table_shapes = {(B, MAXP), (MAXP,)}
        args = {
            "tick": (a._tick_fn(2), (
                _sds((B,), i32), _sds((B,), i32), pt,
                _sds((B,), jnp.uint32), _sds((B,), i32),
                _sds((B,), jnp.float32))),
            "verify": (a._verify_fn(4), (
                _sds((B, 4), i32), _sds((B,), i32), pt)),
            "prefill": (a._prefill_fn(2), (
                _sds((1, 2 * PAGE), i32), _sds((), i32),
                _sds((2,), i32))),
            "prefill_suffix": (a._prefill_suffix_fn(2, 1), (
                _sds((1, 2 * PAGE), i32), _sds((), i32), _sds((), i32),
                _sds((MAXP,), i32))),
        }
        self.out, self.calls_of = {}, {}
        for kind, (fn, rest) in args.items():
            before = {n: len(c) for n, c in self.calls.items()}
            self.out[kind] = jax.eval_shape(fn, a._p, a._blk, self.pool,
                                            *rest)
            self.calls_of[kind] = {
                n: c[before.get(n, 0):] for n, c in self.calls.items()
                if len(c) > before.get(n, 0)}


@pytest.fixture(scope="module")
def traced():
    cache = {}

    def get(family, kv_bits):
        if (family, kv_bits) not in cache:
            cache[family, kv_bits] = _Traced(family, kv_bits)
        return cache[family, kv_bits]
    return get


# --------------------------------------------------------------- the table

@pytest.mark.parametrize("builder", BUILDERS)
def test_unknown_family_is_refused_with_the_tables_keys(builder):
    cfg, params = TINY["gpt2"]()
    build = getattr(serving, builder)
    args = (cfg, "bert") if builder == "cache_spec_from_config" \
        else ("bert", cfg, params)
    with pytest.raises(ValueError) as e:
        build(*args)
    assert str(e.value) == (
        "unknown serving family 'bert' "
        f"(expected one of {sorted(serving.FAMILIES)})")


@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_table_entry_is_the_skeleton_bound_to_its_layer_math(family):
    cls = serving.FAMILIES[family]
    assert issubclass(cls, PagedServingAdapter)
    assert cls is not PagedServingAdapter
    # bound, not forked: the entry defines no method or property
    own = {k for k, v in vars(cls).items()
           if callable(v) or isinstance(v, (property, staticmethod,
                                            classmethod))}
    assert own == set(), own
    assert cls.layer_math.startswith("deepspeed_tpu.models.")
    assert isinstance(cls.math(), types.ModuleType)


def test_old_names_stay_importable_from_both_modules():
    from deepspeed_tpu.serving import adapters
    for name in ("GPT2ServingAdapter", "LlamaServingAdapter"):
        assert getattr(serving, name) is getattr(adapters, name)
    assert serving.FAMILIES["gpt2"] is serving.GPT2ServingAdapter
    assert serving.FAMILIES["llama"] is serving.LlamaServingAdapter


@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_geometry_sizes_the_pool_and_the_prompt_budget(family):
    cfg, params = TINY[family]()
    geom = serving.FAMILIES[family].math().serving_geometry(cfg)
    assert set(geom) == {"n_layers", "kv_heads", "head_dim", "dtype",
                         "max_prompt_len", "vocab_size"}
    spec = serving.cache_spec_from_config(cfg, family, None, slots=SLOTS)
    for k in ("n_layers", "kv_heads", "head_dim", "dtype"):
        assert getattr(spec, k) == geom[k]
    adapter = serving.FAMILIES[family](cfg, params, spec)
    assert adapter.max_prompt_len() == geom["max_prompt_len"]


# ------------------------------------------------- the programs, abstractly

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("kv_bits", [0, 8])
@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_program_gives_the_pool_back_as_it_came(traced, family, kv_bits,
                                                kind):
    t = traced(family, kv_bits)
    assert len(t.pool) == (4 if kv_bits == 8 else 2)
    pool_out, *rest = t.out[kind]
    assert [(x.shape, x.dtype) for x in pool_out] \
        == [(x.shape, x.dtype) for x in t.pool]
    V = t.adapter.cfg.vocab_size
    if kind == "tick":
        toks, logits = rest
        assert (toks.shape, logits.shape) == ((2, SLOTS), (SLOTS, V))
    elif kind == "verify":
        greedy, logits = rest
        assert (greedy.shape, logits.shape) \
            == ((SLOTS, 4), (SLOTS, 4, V))
    else:
        (logits,) = rest
        assert logits.shape == (V,)
    assert logits.dtype == jnp.float32


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_layer_math_never_sees_the_pool_or_the_page_table(traced, family,
                                                          kind):
    for kv_bits in (0, 8):
        t = traced(family, kv_bits)
        calls = t.calls_of[kind]
        assert calls, kind
        for name, shapes_per_call in calls.items():
            for shapes in shapes_per_call:
                seen = set(shapes)
                assert not seen & t.pool_shapes, (name, kind)
                assert not seen & t.table_shapes, (name, kind)


@pytest.mark.parametrize("family", FAMILY_NAMES)
def test_every_layer_math_function_is_one_the_skeleton_calls(traced,
                                                             family):
    """A family writes nothing the skeleton does not use, and both
    families write the same set of functions."""
    t = traced(family, 0)
    assert set(t.calls) == set(t.functions)
    assert t.functions == traced(FAMILY_NAMES[0], 0).functions
    rows = {n for n in t.functions if n.startswith("serving_row_")}
    prompt = {n for n in t.functions if n.startswith("serving_prompt_")}
    for kind in ("tick", "verify"):
        assert set(t.calls_of[kind]) == rows
    for kind in ("prefill", "prefill_suffix"):
        assert set(t.calls_of[kind]) == prompt


def test_one_program_per_kind_and_static_size(traced):
    a = traced("gpt2", 0).adapter
    assert a._tick_fn(2) is a._tick_fn(2)
    assert a._tick_fn(2) is not a._tick_fn(1)
    assert a._prefill_suffix_fn(2, 1) is not a._prefill_suffix_fn(1, 2)
    with pytest.raises(AssertionError, match="position budget"):
        a._prefill_fn(a.max_prompt_len() // PAGE + 1)


# ----------------------------------------------- training imports no serving

def test_training_imports_none_of_the_serving_files():
    """The arrow from training to serving points nowhere: importing the
    package and every benchmark family loads no serving module, so a
    change fenced to these files cannot reach a training cell."""
    code = (
        "import sys, importlib, pkgutil\n"
        "import deepspeed_tpu\n"
        "import benchmark.families as bf\n"
        "for m in pkgutil.iter_modules(bf.__path__):\n"
        "    importlib.import_module('benchmark.families.' + m.name)\n"
        "bad = [m for m in sys.modules if m.startswith("
        "'deepspeed_tpu.serving') or m in ("
        "'deepspeed_tpu.models.gpt2_inference', "
        "'deepspeed_tpu.models.llama_inference')]\n"
        "print('LOADED', bad)\n"
        # and serving itself binds a family's model file on first use
        "import deepspeed_tpu.serving\n"
        "print('EAGER', [m for m in sys.modules if m.endswith("
        "'_inference')])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "LOADED []" in out.stdout, out.stdout
    assert "EAGER []" in out.stdout, out.stdout
