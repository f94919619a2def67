"""What Xing4.0 adds to the DeepSeek-V3 model, on the CPU at small sizes:
the program's model — compressed queries under YaRN, four residual streams
mixed by Sinkhorn-normalised matrices round every branch, the
multi-token-prediction module scored at offset 2 against the shared head —
against the benchmark's plain reference (``benchmark/reference/xing4.py``),
branch by branch, coefficient by coefficient and leaf by leaf, both losses;
the reference's walked gradient against ``jax.grad``; each named omission
failing the benchmark's check; ``H_res`` doubly stochastic and the gauge that
says how far; one stream, no prediction module and whole queries being the
Kanana-2 program; the 8 expert shares adding up round the streams; the scopes
and gauges in the engine's step. Seeded weights, float32; each tiny model is
built once a module and every gradient is one jitted program.
"""

import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import xing4 as fam
from benchmark.reference import deepseek_v3 as ref_v3, xing4 as ref
from deepspeed_tpu.models import hyper_connections as hc
from deepspeed_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                              DeepseekV3ForCausalLM,
                                              deepseek_v3_tiny, rope_tables,
                                              xing4_tiny, yarn_mscale)
from deepspeed_tpu.moe.dropless import DroplessMoE
from tests.cell_config import config_file

FILE = config_file("xing4-29b-a4b-ep8-depth5")
TOL = FILE["train"]["tolerance"]
B, S = 2, 64


def _float32(config, **sizes):
    """The configuration's rehearsal sizes with every dtype float32, the
    mixers' offsets drawn wide (Sinkhorn's first round is then far from its
    twentieth) and a clamp NARROW enough to bind (the published +-30 never
    does at these magnitudes, and a clamp that never binds cannot be
    missed)."""
    config = copy.deepcopy(config)
    config["rehearse_cpu"]["model"]["dtype"] = "float32"
    engine = config["rehearse_cpu"]["train"]["engine"]
    engine["bf16"] = {"enabled": False}
    engine["data_types"] = {"grad_dtype": "fp32"}
    config["rehearse_cpu"].update(hc_bias_std=1.5, mhc_h_res_clamp_min=-1.2,
                                  mhc_h_res_clamp_max=1.2, **sizes)
    return config


@pytest.fixture(scope="module")
def tiny():
    """(config, weights, ids, the system's step, the comparison): the file's
    rehearsal — one dense + one expert layer + the prediction module, one of
    four expert shares held — with every vector and narrow matrix moved off
    its initial value so that a weight left out cannot pass."""
    config = _float32(FILE)
    vocab = fam.sizes(config, True)["vocab_size"]
    ids = np.random.default_rng(0).integers(0, vocab, (B, S)).astype(np.int32)
    params = jax.jit(fam._model(config, True).init)(
        jax.random.PRNGKey(0), jnp.asarray(ids))["params"]
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 1000))
    params = jax.tree_util.tree_map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.shape[-1] < 64 or x.ndim == 1 else x, params)
    # the scores far from uniform, so that the queries' norm, the YaRN
    # blend and its scale show
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 6.0 * x if any(
            getattr(k, "key", None) in ("q_b_proj", "kv_a_proj", "kv_b_proj")
            for k in path) else x, params)
    system = fam.system_step(config, params, ids, jax.devices()[0], True)
    compared = fam.compare(config, params, ids, jax.devices()[0], True,
                           system)
    return config, params, ids, system, compared


def test_system_matches_reference_branch_coefficient_and_leaf(tiny):
    config, _, _, system, (loss, gnorm, diffs) = tiny
    assert float(system[0]) == pytest.approx(loss, abs=2e-5)
    assert diffs["system_grad_norm"] == pytest.approx(gnorm, rel=1e-4)
    # both losses, each alone
    assert diffs["system_mtp_loss"] == pytest.approx(
        diffs["reference_mtp_ce"], abs=2e-5)
    assert loss == pytest.approx(
        diffs["reference_ce"] + 0.3 * diffs["reference_mtp_ce"], abs=1e-6)
    assert diffs["routing_differs"] == 0
    # the expert layer and the prediction module's: T x k assignments each
    assert diffs["routing_assignments"] == 2 * B * S * 2
    for branch in ("mla_out_rel", "dense_out_rel", "ffn_out_rel"):
        assert 0 < diffs[branch] < 2e-5, branch
    assert 0 < diffs["mhc_coeff_abs"] < 5e-6
    assert set(diffs["pinned_by_block"]) == {"0", "1", "mtp"}
    # not pinned: float32 on both sides, so every layer and the mixes agree
    assert max(max(r) for r in diffs["own_stream_by_layer"]) < 1e-4
    assert max(diffs["mtp_own_stream"]) < 1e-4
    assert diffs["stream_mix_rel"] < 1e-6
    assert len(diffs["stream_mix_by_layer"]) == 3
    leaves = diffs["grad_leaf_rel"]
    assert set(leaves) == set(TOL["grad_leaf_rel"])
    assert {"attn.q_a", "attn.q_a_norm", "attn.q_b", "hc.phi", "hc.gate",
            "hc.bias", "mtp.eh_proj", "embed", "lm_head"} <= set(leaves)
    assert max(leaves.values()) < 2e-4, leaves
    assert diffs["bias_grad_abs"] == 0.0
    checks, _ = fam.judge_train(config, float(system[0]),
                                diffs["system_grad_norm"], loss, gnorm, diffs)
    assert all(checks.values()), checks
    assert {"stream_coefficients_match_reference", "stream_mixes_add_up",
            "prediction_loss_matches_reference"} <= set(checks)


def test_the_walked_gradient_is_jax_grads(tiny):
    """``pinned_backward`` handed the reference's OWN streams and experts
    gives the gradient ``jax.grad`` gives of the plain forward pass, every
    leaf — the embedding's and the head's sums of two uses among them."""
    config, params, ids, _, _ = tiny
    weights = fam.reference_view(params, config, True)
    sizes = fam.reference_sizes(config, True)
    ids = jnp.asarray(ids)

    @jax.jit
    def both(weights):
        (loss, detail), want = ref.loss_and_grads(weights, ids, **sizes)
        own = {"layers": detail["layers"], "mtp": detail["mtp"]}
        ces, got = ref.pinned_backward(*weights, ids, own,
                                       lambda where, g, at: g, **sizes)
        return loss, ces, want, {str(k): g for k, g in got.items()}

    loss, (ce, mtp_ce), (top, layers, mtp), got = both(weights)
    assert float(loss) == pytest.approx(float(ce) + 0.3 * float(mtp_ce),
                                        rel=1e-6)
    walked = (got["top"], [got[str(i)] for i in range(len(layers))],
              got["mtp"])
    for (path, want), have in zip(
            jax.tree_util.tree_leaves_with_path((top, layers, mtp)),
            jax.tree_util.tree_leaves(walked)):
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        assert float(jnp.max(jnp.abs(want - have))) <= 2e-4 * scale, path
    # both uses reach the two shared leaves
    for leaf in ("embed", "lm_head"):
        alone = jax.jit(lambda w: ref.loss_and_grads(
            w, ids, **dict(sizes, mtp_weight=0.0)))(weights)[1][0][leaf]
        assert float(jnp.max(jnp.abs(top[leaf] - alone))) > 1e-3


# (the omission, what the reference is told instead, the reading that must
# say so: a branch of layer 0 or of the prediction module on its OWN stream,
# layer 0's attention coefficients, or the prediction loss)
OMISSIONS = [
    ("H_pre's token term zeroed", {"mix_over": {"zero_dynamic": "pre"}},
     "coeff"),
    ("H_post's token term zeroed", {"mix_over": {"zero_dynamic": "post"}},
     "coeff"),
    ("H_res's token term zeroed", {"mix_over": {"zero_dynamic": "res"}},
     "coeff"),
    ("Sinkhorn stopped at 1 round", {"iters": 1}, "coeff"),
    ("the clamp left out", {"clamp": None}, "coeff"),
    ("the exp left out", {"mix_over": {"use_exp": False}}, "coeff"),
    ("H_post without its 2", {"mix_over": {"post_scale": 1.0}}, "coeff"),
    ("YaRN's scale left out of the scores",
     {"attn_over": {"yarn_score_scale": False}}, "mixer"),
    ("plain frequencies for YaRN's blend",
     {"attn_over": {"yarn_blend": False}}, "mixer"),
    ("the queries' latent not normed", {"attn_over": {"query_norm": False}},
     "mixer"),
    ("the prediction scored at offset 1", {"mtp_offset": 1}, "mtp_loss"),
    ("eh_proj's halves the other way round", {"mtp_h_first": False},
     "mtp_mixer"),
]


@pytest.mark.parametrize("omission,override,reading", OMISSIONS,
                         ids=[o[0] for o in OMISSIONS])
def test_each_omission_fails_the_check(tiny, omission, override, reading):
    """The reference WITH the omission is a model the system is not: the
    comparison must say so by three times the file's limit, in the reading
    the omission is in."""
    config, params, ids, (_, seen, _), _ = tiny
    sizes = dict(fam.reference_sizes(config, True), **override)
    _, detail = jax.jit(lambda p: ref.loss(
        p, jnp.asarray(ids), lambda w: fam.reference_view(w, config, True),
        **sizes))(params)
    first, want = seen["layers"][0], detail["layers"][0]
    if reading == "coeff":
        # layer 0's attention branch starts from the same embedding copy
        got = float(fam._coeff_abs(first["attn_hc"], want["attn_hc"]))
        assert not got <= 3 * TOL["mhc_coeff_abs"], (omission, got)
    elif reading == "mixer":
        got = float(fam._rel(first["mixer_out"], want["mixer_out"]))
        assert not got <= 3 * TOL["mla_out_rel"], (omission, got)
    elif reading == "mtp_mixer":
        got = float(fam._rel(seen["mtp"]["mixer_out"],
                             detail["mtp"]["mixer_out"]))
        assert not got <= 3 * TOL["mla_out_rel"], (omission, got)
    else:
        got = abs(float(seen["mtp_loss"]) - float(detail["mtp_ce"]))
        assert not got <= 3 * TOL["mtp_loss_abs"], (omission, got)


def test_h_res_is_doubly_stochastic_after_20_rounds_and_the_gauge_says_so():
    """At the spread the configuration draws (log-entries of std ~0.8) rows
    sum to one to float32 rounding (the last normalisation) and columns to
    within 1e-4 after 20 rounds, and not after 1 (a wider spread converges
    slower: 1e-2 at std 1.5); ``res_sum_err`` is the larger of the two; the
    model sows the LARGEST of its branches' under ``mhc_res_sum_err`` and the
    engine's fold keeps it a maximum."""
    m = jnp.exp(0.8 * jax.random.normal(jax.random.PRNGKey(0), (4, 4, 4096)))
    h = hc.sinkhorn(m, 20)
    assert float(jnp.max(jnp.abs(h.sum(axis=1) - 1))) < 1e-6
    assert float(jnp.max(jnp.abs(h.sum(axis=0) - 1))) < 1e-4
    wide = jnp.exp(1.5 * jax.random.normal(jax.random.PRNGKey(0),
                                           (4, 4, 4096)))
    assert 1e-3 < float(hc.res_sum_err(hc.sinkhorn(wide, 20))) < 0.1
    assert float(hc.res_sum_err(h)) == pytest.approx(
        float(jnp.max(jnp.abs(h.sum(axis=0) - 1))), abs=1e-7)
    assert float(hc.res_sum_err(hc.sinkhorn(m, 1))) > 0.05
    # the reference's loop over [.., n, n] gives the same matrix
    p = {"phi": jnp.zeros((8, 24)), "gate": jnp.ones((3,)),
         "bias": jnp.concatenate([jnp.zeros((8,)),
                                  jnp.log(m[:, :, 0]).ravel()])}
    _, _, want = ref.stream_coefficients(jnp.ones((1, 1, 4, 2)), p,
                                         hc_eps=1e-6, iters=20, clamp=None)
    np.testing.assert_allclose(want[0, 0], h[:, :, 0], rtol=1e-5)

    from deepspeed_tpu.runtime.engine import _mean_by_name
    sown = {"layer_0": {"attn_hc": {"mhc_res_sum_err": (jnp.float32(1e-6),)},
                        "mlp": {"moe_dropped_rows": (jnp.float32(2.0),)}},
            "layer_1": {"attn_hc": {"mhc_res_sum_err": (jnp.float32(3e-6),)},
                        "mlp": {"moe_dropped_rows": (jnp.float32(4.0),)}}}
    folded = _mean_by_name(sown, DeepseekV3ForCausalLM.stat_maxima)
    assert float(folded["mhc_res_sum_err"]) == pytest.approx(3e-6)
    assert float(folded["moe_dropped_rows"]) == pytest.approx(3.0)


def test_one_stream_no_prediction_and_whole_queries_is_the_kanana_program():
    """Every new key at its default is ``deepseek_v3_tiny``: the same config,
    the same leaves and — against a config that spells the defaults out —
    the same logits and loss bit for bit; and the new keys name no leaf
    there."""
    plain = deepseek_v3_tiny()
    spelt = xing4_tiny(q_lora_rank=None, rope_theta=1000000.0,
                       rope_scaling=None, hc_mult=1, hc_phi_std=0.02,
                       hc_gate_mean=1.0, hc_gate_std=0.0, hc_bias_std=0.0,
                       num_nextn_predict_layers=0)
    assert spelt == plain
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 24)),
                      jnp.int32)
    model = DeepseekV3ForCausalLM(plain)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    names = {getattr(k, "key", None) for path, _
             in jax.tree_util.tree_leaves_with_path(params) for k in path}
    assert not names & {"attn_hc", "ffn_hc", "q_a_proj", "q_a_norm",
                        "q_b_proj", "mtp_layer", "mtp_eh_proj"}
    assert "q_proj" in names
    # one stream is x + branch(x): a mixer that reads the stream whole,
    # mixes it with the identity and writes the branch back at one
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 16))
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 16))
    one = jnp.ones((1, 8))
    np.testing.assert_array_equal(hc.read(x, one), x)
    np.testing.assert_array_equal(hc.write(x, y, one, one[None]), x + y)
    assert plain.softmax_scale == 1.0 / np.sqrt(48)
    assert model.stat_gauges.keys() == {
        "moe_aux_loss", "moe_z_loss", "moe_rows_max_over_mean",
        "moe_dropped_rows"}
    with pytest.raises(NotImplementedError,
                       match="rope_scaling type 'linear'"):
        DeepseekV3Config(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(NotImplementedError, match="predict_layers=2"):
        DeepseekV3Config(num_nextn_predict_layers=2)


def test_yarn_by_hand():
    """DeepSeek-V3's form at the published numbers: the softmax scale is
    192^-0.5 x (0.1 ln 64 + 1)^2 = 2.0047 x, cos and sin carry mscale /
    mscale_all_dim = 1, the fastest pair turns at the plain frequency and
    the slowest at 1/64 of it."""
    cfg = fam.model_config(FILE, False)
    assert yarn_mscale(64, 1) == pytest.approx(1.41589, abs=1e-5)
    assert cfg.softmax_scale * np.sqrt(192) == pytest.approx(2.0047, abs=1e-4)
    cos, sin = rope_tables(cfg, jnp.arange(4096))
    assert cos.shape == (4096, 32)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, 1.0, atol=1e-5)
    assert float(cos[1, 0]) == pytest.approx(np.cos(1.0), abs=1e-6)
    slow = 10000.0 ** (-62 / 64) / 64
    assert float(sin[4095, 31]) == pytest.approx(np.sin(4095 * slow),
                                                 abs=1e-5)
    inv = ref.yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    np.testing.assert_allclose(jnp.arcsin(sin[1]), inv, rtol=2e-4)


# ------------------------------------------------ the expert layer's shares

H, E, K, F, FS, RANKS, N = 32, 64, 4, 24, 24, 8, 4


def test_the_eight_shares_round_the_streams_are_the_whole_layer():
    """The parts all 8 ranks give of an expert branch (each its 8 experts'
    rows; rank 0 with the shared expert, the others without), written back
    through ONE stream mix, add up to the uncut reference's branch."""
    ks = jax.random.split(jax.random.PRNGKey(0), 12)
    n = lambda k, *s: 0.3 * jax.random.normal(k, s)  # noqa: E731
    p = {"router": n(ks[0], H, E), "bias": n(ks[1], E),
         "gate": n(ks[2], E, H, F), "up": n(ks[3], E, H, F),
         "down": n(ks[4], E, F, H), "shared_gate": n(ks[5], H, FS),
         "shared_up": n(ks[6], H, FS), "shared_down": n(ks[7], FS, H)}
    mixer = {"phi": n(ks[8], N * H, 2 * N + N * N), "gate": n(ks[9], 3) + 1,
             "bias": n(ks[10], 2 * N + N * N)}
    X = jax.random.normal(ks[11], (2, 24, N, H))
    mix = dict(hc_eps=1e-6, iters=20, clamp=(-30.0, 30.0))

    def layer(held, rank, shared):
        return DroplessMoE(E, K, F, norm_topk_prob=True, balance_coeff=0.0,
                           z_coeff=0.0, dtype=jnp.float32, experts_held=held,
                           expert_share=rank, shared_d_ff=shared,
                           routed_scale=2.0, shared_gate=False,
                           score="sigmoid", choice_bias=True)

    def leaves(lo, held, shared):
        out = {"router": p["router"], "e_score_correction_bias": p["bias"],
               "gate_proj": p["gate"][lo:lo + held],
               "up_proj": p["up"][lo:lo + held],
               "down_proj": p["down"][lo:lo + held]}
        if shared:
            out.update(shared_gate_proj=p["shared_gate"],
                       shared_up_proj=p["shared_up"],
                       shared_down_proj=p["shared_down"])
        return out

    @jax.jit
    def both(X):
        with jax.default_matmul_precision("highest"):
            whole, _, (h_pre, h_post, h_res) = ref.branch(
                X, mixer, lambda u: ref_v3.experts(
                    u.reshape(-1, H), p, K, 0, routed_scale=2.0)[0].reshape(
                        u.shape), **mix)
            flat = X.reshape(2, 24, N * H)
            t = lambda c: jnp.moveaxis(  # noqa: E731
                c.reshape(48, *c.shape[2:]), 0, -1)
            u = hc.read(flat, t(h_pre))
            held = E // RANKS
            parts = sum(layer(held, rank, FS if rank == 0 else 0).apply(
                {"params": leaves(rank * held, held, rank == 0)}, u)
                for rank in range(RANKS))
            return whole, hc.write(flat, parts, t(h_post), t(h_res))

    whole, got = both(X)
    np.testing.assert_allclose(got.reshape(whole.shape), whole, atol=3e-4)


# ------------------------------------------------------------- the engine

def test_scopes_and_gauges_reach_the_engines_step():
    """ISSUE 56's names in the compiled step's ``op_name``s — the three
    stream scopes in a block, ``mtp`` round the whole prediction module with
    its second head pass still ``ds_loss_head`` — and its two gauges; a
    rematted block keeps its input whatever its rank."""
    import re
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.telemetry import default_registry, spans
    from tests.simple_model import base_config
    default_registry().reset()
    cfg = xing4_tiny(num_hidden_layers=2, experts_held=4, loss_chunk=16,
                     remat=True)
    engine, _, _, _ = dstpu.initialize(config=base_config(),
                                       model=DeepseekV3ForCausalLM(cfg))
    batch = {"input_ids": np.random.RandomState(0).randint(
        0, 256, (8, 32)).astype(np.int32)}
    first = float(engine.train_batch(batch))
    gauges = engine.telemetry_flush()["gauges"]
    assert 0 <= gauges["mhc/res_sum_err"] < 5e-3
    assert 4.0 < gauges["mtp/loss"] < 7.0 and np.isfinite(first)
    assert "moe/rows_held_share" in gauges
    hlo = engine.lower_train_step(batch).compile().as_text()
    for scope in ("layer_0/attn_hc/mhc_coeff", "layer_1/ffn_hc/mhc_coeff",
                  "layer_0/mhc_read", "layer_1/mhc_write",
                  "mla_attn/mla_latent/q_a_proj", "mla_attn/q_b_proj",
                  "mtp/mtp_eh_proj", "mtp/ds_embed", "mtp/ds_loss_head",
                  "mtp/mtp_layer/mla_attn", "mtp/mtp_layer/mlp/moe_router",
                  "mtp/mtp_layer/ffn_hc/mhc_coeff"):
        assert re.search(r'op_name="[^"]*/' + scope + "[/\"]", hlo), scope
    for name in ("mhc_coeff", "mhc_read", "mhc_write", "mtp"):
        assert name in spans.annotate.__doc__, name
    assert float(engine.train_batch(batch)) < first


def test_the_published_shapes_count_30_28_b_and_29_51_b_without_mtp():
    whole = DeepseekV3Config(**{**{
        k: FILE["published"].get(k, FILE[k]) for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
            "first_k_dense_replace", "hc_mult", "num_nextn_predict_layers")},
        "rope_scaling": FILE["rope_scaling"]})
    assert whole.attention_params() == 28_411_136
    assert whole.stream_mixer_params() == 344_091
    assert whole.layer_params(False) == 28_411_136 + 7_168 + 2 * 344_091 \
        + 99_090_432
    assert whole.num_params() == 30_276_195_174
    assert whole.num_params() - whole.mtp_params() == 29_505_505_264
