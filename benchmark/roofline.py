"""Operations and bytes an algorithm needs, and the chip's peaks.

A roofline share is ``needed work / peak / measured time``; the needed work
is computed here from shapes, by the benchmark and not by the program, and
counts nothing that is recomputed (remat, the flash backward's second
QK^T). Peaks come from ``peaks.json`` by ``device_kind``; a device that is
not in the table is an error, never a default.
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind, path=None):
    with open(path or os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks recorded for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def dense_matmul_params(n_layer, n_embd, vocab_size):
    """Parameters a token is multiplied with in a dense GPT-style decoder:
    per layer QKV 3E^2 + projection E^2 + MLP 8E^2, plus the output head
    V*E (the embedding lookup is a gather, not a matmul)."""
    return n_layer * 12 * n_embd * n_embd + vocab_size * n_embd


def dense_train_flops_per_token(n_layer, n_embd, vocab_size, seq_len):
    """Forward + backward flops one token requires: 6 per matmul parameter
    (2 forward, 4 backward) plus causal attention. Full attention costs
    4*S*E forward per token per layer (QK^T and PV, 2*S*E each); backward
    twice that; a causal model needs half: 6*S*E per layer."""
    return 6 * dense_matmul_params(n_layer, n_embd, vocab_size) \
        + 6 * n_layer * seq_len * n_embd


def causal_attention_train_flops(batch, n_head, seq_len, head_dim):
    """Flops one layer's attention needs forward + backward under a causal
    mask: six S x S x D matmuls (QK^T, PV; dV, dP, dQ, dK), each 2*S^2*D
    per head, halved by the mask."""
    return 6 * batch * n_head * seq_len * seq_len * head_dim


def kv_read_bytes(n_layer, n_embd, contexts, itemsize):
    """Bytes of K and V one decode step reads for slots whose contexts hold
    ``contexts`` tokens: every layer reads K and V rows of width E."""
    return 2 * n_layer * n_embd * itemsize * int(sum(contexts))


def share(needed, peak_per_s, seconds):
    """needed work / peak / time, as a percentage; None without time."""
    if not seconds or seconds <= 0:
        return None
    return 100.0 * needed / peak_per_s / seconds
