"""Operations and bytes the convolution-and-attention, routed-experts LM
*requires*, from shapes alone (see ``required_work.py`` for the rules:
recomputation is not counted, a product of [m, k] by [k, n] is ``2 m k n``
operations).

The products: every convolution layer's ``in_proj`` (E -> 3 E) and
``out_proj``, the attention layers' ``wqkv`` and ``wo`` and their scores
over the causal triangle (every query head; key/value heads are shared,
their products are not), the dense layers' gated MLP, the router, **the
routed experts at their expectation** (a token's ``k`` slots fall on the
experts held here with probability ``held / n`` each: 4 x 8 / 32 = 1 expert
a token in the LFM2 cut, whatever a step's routing really was; the run
prints the share that landed) and the tied head.  The gated convolution's
few operations an element (``2 K + 2``) are not products and are left out
of the step's count; its kernels are bound by HBM bandwidth, and their
roofline is its bytes: forward reads ``b``, ``c``, ``u`` and writes ``y``;
backward reads ``b``, ``c``, ``u``, ``dy`` and writes ``db``, ``dc``,
``du``, each ``[B, T, E]`` once at the true width (the halo's 8 rows a tile
and the taps are not counted).
"""
from .required_work import BF16, F32, _roof
from .required_work_swa_moe_lm import pairs_a_head
from .weights_conv_moe_lm import sizes


def expected_experts_per_token(m):
    s = sizes(m)
    return s["k"] * s["held"] / s["n"]


def matmul_params_per_token(m):
    """Parameters a token meets in matrix products, forward."""
    s = sizes(m)
    e = s["e"]
    conv = e * 3 * e + e * e
    attn = e * s["qkv"] + s["q"] * e
    dense = 3 * e * s["f"]
    moe = e * s["n"] + expected_experts_per_token(m) * 3 * e * s["fe"]
    return (s["conv"] * conv + s["attn"] * attn + s["dense"] * dense
            + s["moe"] * moe + e * s["v"])


def attention_forward_flops(m, batch, seq):
    """QK^T and PV of every attention layer, every query head, over the
    causal triangle."""
    s = sizes(m)
    return (s["attn"] * 2 * 2 * batch * pairs_a_head(seq, 0) * s["heads"]
            * s["d"])


def train_flops_per_step(m, batch, seq):
    """Forward plus backward (the backward counted as two forwards)."""
    return 3 * (2 * batch * seq * matmul_params_per_token(m)
                + attention_forward_flops(m, batch, seq))


def flash_required_per_step(m, batch, seq, peaks):
    """{"flash_fwd", "flash_dq", "flash_dkv": {flops, bytes, bound, min_s}}
    of the attention kernels in one train step, at the head's true width
    ``D`` (64: a kernel that pads its lanes to 128 reads low, never over
    100 %).  Forward: two products over the causal pairs; reads q (every
    query head), k, v (every key/value head), writes o and the log-sum-exp.
    Backward: four products, two counted with each kernel; dQ reads q k v dO
    and the two row statistics and writes dQ, dK/dV reads the same and
    writes dK and dV."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    tokens = batch * seq
    wide = tokens * s["heads"] * s["d"] * store       # q, o, dO, dQ: each
    narrow = tokens * s["kv"] * s["d"] * store        # k, v, dK, dV: each
    row = batch * s["heads"] * seq * F32              # lse, delta: each
    lay = s["attn"]
    flops = attention_forward_flops(m, batch, seq)
    return {
        "flash_fwd": _roof(flops, lay * (2 * wide + 2 * narrow + row),
                           peaks),
        "flash_dq": _roof(flops, lay * (3 * wide + 2 * narrow + 2 * row),
                          peaks),
        "flash_dkv": _roof(flops, lay * (2 * wide + 4 * narrow + 2 * row),
                           peaks),
    }


def gmm_required_per_step(m, batch, seq, peaks):
    """{"gmm_fwd", "gmm_dx", "gmm_dw"} of the grouped products of all expert
    layers in one train step: gate, up and down, each over the expected live
    rows ``R = tokens k held / n``."""
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


def short_conv_required_per_step(m, batch, seq, peaks):
    """{"short_conv_fwd", "short_conv_bwd"} of every convolution layer in
    one train step: ``2 K + 2`` operations an element forward, ``4 K + 6``
    backward; forward 3 planes read and 1 written, backward 4 read and 3
    written, each ``[batch, seq, E]`` in the model's type."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    plane = batch * seq * s["e"]
    lay, k = s["conv"], s["K"]
    return {
        "short_conv_fwd": _roof(lay * (2 * k + 2) * plane,
                                lay * 4 * plane * store, peaks),
        "short_conv_bwd": _roof(lay * (4 * k + 6) * plane,
                                lay * 7 * plane * store, peaks),
    }


def pallas_required_per_step(m, batch, seq, peaks):
    """Every Pallas kernel family on the step's path: flash forward and
    both backward kernels, rmsnorm twice a layer and once before the head,
    softmax cross-entropy over float32 logits, the grouped products, the
    gated convolution."""
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
    out.update(short_conv_required_per_step(m, batch, seq, peaks))
    return out
