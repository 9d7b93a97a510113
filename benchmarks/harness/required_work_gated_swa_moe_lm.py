"""Operations and bytes the gated window-and-full-attention, routed-experts LM
*requires*, from shapes alone (see ``required_work.py`` for the rules:
recomputation is not counted, a product of [m, k] by [k, n] is ``2 m k n``
operations).

The products: every layer's ``wqkv``, ``wo`` and output gate at the
layer's own query heads, the attention scores **over the pairs the layer's
mask keeps** (the causal triangle on a full layer, the band ``0 <= t - j <
W`` alone on a window layer: `required_work_swa_moe_lm.pairs_a_head`; every
query head counted, key/value heads are shared, their products are not),
the dense layer's gated MLP, the router, the shared expert, **the routed
experts at their expectation** (a token's ``k`` slots fall on the experts
held here with probability ``held / n`` each: 10 x 16 / 256 = 0.625
experts a token in the Laguna cut, whatever a step's routing really was;
the run prints the share that landed) and the untied head.
"""
from .required_work import BF16, F32, _roof
from .required_work_swa_moe_lm import pairs_a_head
from .weights_gated_swa_moe_lm import sizes


def expected_experts_per_token(m):
    s = sizes(m)
    return s["k"] * s["held"] / s["n"]


def attention_params(m, heads):
    """One layer's attention matrices at ``heads`` query heads: wqkv, wo
    and the output gate."""
    s = sizes(m)
    e, d = s["e"], s["d"]
    return e * (heads + 2 * s["kv"]) * d + heads * d * e + e * heads


def matmul_params_per_token(m):
    """Parameters a token meets in matrix products, forward (the embedding
    is a lookup)."""
    s = sizes(m)
    e = s["e"]
    attn = sum(attention_params(m, h) for h in s["heads"])
    dense = 3 * e * s["f"]
    moe = (e * s["n"] + 3 * e * s["fs"]
           + expected_experts_per_token(m) * 3 * e * s["fe"])
    return attn + s["dense"] * dense + s["moe"] * moe + e * s["v"]


def attention_forward_flops(m, batch, seq, layers=None):
    """QK^T and PV of the ``layers`` named (all: None), every query head,
    over the pairs each layer's mask keeps."""
    s = sizes(m)
    layers = range(s["layers"]) if layers is None else layers
    return sum(2 * 2 * batch * pairs_a_head(seq, s["windows"][i])
               * s["heads"][i] * s["d"] for i in layers)


def train_flops_per_step(m, batch, seq):
    """Forward plus backward (the backward counted as two forwards)."""
    return 3 * (2 * batch * seq * matmul_params_per_token(m)
                + attention_forward_flops(m, batch, seq))


def window_layers(m):
    """The layers that attend over a band."""
    return [i for i, w in enumerate(sizes(m)["windows"]) if w]


def flash_required_per_step(m, batch, seq, peaks, layers=None):
    """{"flash_fwd", "flash_dq", "flash_dkv": {flops, bytes, bound, min_s}}
    of the attention kernels of the ``layers`` named (all: None) in one
    train step.  Forward: two products over the kept pairs; reads q (every
    query head), k, v (every key/value head: feeding each to its query
    heads is the implementation's business), writes o and the log-sum-exp.
    Backward: four products (dV, dP, dQ, dK), two counted with each kernel;
    dQ reads q k v dO and the two row statistics and writes dQ, dK/dV reads
    the same and writes dK and dV."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    layers = range(s["layers"]) if layers is None else layers
    tokens = batch * seq
    wide = sum(s["heads"][i] for i in layers) * tokens * s["d"] * store
    narrow = len(layers) * tokens * s["kv"] * s["d"] * store
    row = sum(s["heads"][i] for i in layers) * tokens * F32
    flops = attention_forward_flops(m, batch, seq, layers)
    return {
        "flash_fwd": _roof(flops, 2 * wide + 2 * narrow + row, peaks),
        "flash_dq": _roof(flops, 3 * wide + 2 * narrow + 2 * row, peaks),
        "flash_dkv": _roof(flops, 2 * wide + 4 * narrow + 2 * row, peaks),
    }


def gmm_required_per_step(m, batch, seq, peaks):
    """{"gmm_fwd", "gmm_dx", "gmm_dw"} of the grouped products of all expert
    layers in one train step: gate, up and down, each over the expected live
    rows ``R = tokens k held / n``; each reads its rows and the held
    experts' matrix and writes its result (``dW``: reads both sets of rows,
    writes the matrix's gradient)."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    rows = batch * seq * expected_experts_per_token(m)
    e, fe, held, lay = s["e"], s["fe"], s["held"], s["moe"]
    flops = lay * 3 * 2 * rows * e * fe
    act = lay * 3 * rows * (e + fe) * store
    mats = lay * 3 * held * e * fe * store
    return {"gmm_fwd": _roof(flops, act + mats, peaks),
            "gmm_dx": _roof(flops, act + mats, peaks),
            "gmm_dw": _roof(flops, act + mats, peaks)}


def pallas_required_per_step(m, batch, seq, peaks):
    """Every Pallas kernel family on the step's path: flash forward and
    both backward kernels over the pairs each layer's mask keeps, rmsnorm
    twice a layer and once before the head, softmax cross-entropy over
    float32 logits, the grouped products."""
    s = sizes(m)
    e, vocab, tokens, lay = s["e"], s["v"], batch * seq, s["layers"]
    store = BF16 if m["dtype"] == "bfloat16" else F32
    out = flash_required_per_step(m, batch, seq, peaks)
    wide = (2 * lay + 1) * tokens * e                           # elements
    out["rmsnorm_fwd"] = _roof(4 * wide, 2 * wide * store, peaks)
    out["rmsnorm_bwd"] = _roof(8 * wide, 3 * wide * store, peaks)
    logits = tokens * vocab * F32
    out["xent_fwd"] = _roof(4 * tokens * vocab, logits, peaks)
    out["xent_bwd"] = _roof(4 * tokens * vocab, 2 * logits, peaks)
    out.update(gmm_required_per_step(m, batch, seq, peaks))
    return out
