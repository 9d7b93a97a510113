"""Operations and bytes the window-and-full-attention, routed-experts LM
*requires*, from shapes alone (see ``required_work.py`` for the rules:
recomputation is not counted, a product of [m, k] by [k, n] is ``2 m k n``
operations).

**Attention is counted over the pairs a layer's mask keeps**: on a full
layer the causal triangle, ``T (T + 1) / 2`` (query, key) pairs a head; on a
window layer the band ``0 <= t - j < W`` alone, ``W (W + 1) / 2 + (T - W) W``
(58,722,304 of the causal 134,225,920 at ``T`` 16,384, ``W`` 4,096), so no
share of a peak or of a roofline can pass 100 % by counting work the kernels
skip.  Every query head is counted (key/value heads are shared, their
products are not).  **The routed experts are counted at their
expectation**: a token's ``k`` slots fall on the experts held here with
probability ``held / n`` each, so it meets ``k held / n`` routed experts (6
x 16 / 64 = 1.5 in the SmallThinker cut) whatever a step's routing really
was; the run prints the share that landed beside its comparison.
"""
from .required_work import BF16, F32, _roof
from .weights_swa_moe_lm import sizes


def expected_experts_per_token(m):
    s = sizes(m)
    return s["k"] * s["held"] / s["n"]


def pairs_a_head(seq, window):
    """(query, key) pairs one head's mask keeps over a sequence: the causal
    triangle, or with ``window`` > 0 the band ``0 <= t - j < window``."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_params(m):
    """One layer's attention matrices: wqkv and wo."""
    s = sizes(m)
    return s["e"] * s["qkv"] + s["q"] * s["e"]


def matmul_params_per_token(m):
    """Parameters a token meets in matrix products, forward: every layer's
    attention matrices, its router and the *expected* routed experts, and
    the head (the embedding is a lookup)."""
    s = sizes(m)
    e = s["e"]
    layer = (attention_params(m) + e * s["n"]
             + expected_experts_per_token(m) * 3 * e * s["fe"])
    return s["layers"] * layer + e * s["v"]


def attention_forward_flops(m, batch, seq, window):
    """QK^T and PV of one layer, every query head, over the pairs its mask
    keeps."""
    s = sizes(m)
    return 2 * 2 * batch * pairs_a_head(seq, window) * s["heads"] * s["d"]


def train_flops_per_step(m, batch, seq):
    """Forward plus backward (the backward counted as two forwards)."""
    s = sizes(m)
    fwd = (2 * batch * seq * matmul_params_per_token(m)
           + sum(attention_forward_flops(m, batch, seq, w)
                 for w in s["windows"]))
    return 3 * fwd


def flash_required_per_step(m, batch, seq, peaks):
    """{"flash_fwd", "flash_dq", "flash_dkv": {flops, bytes, bound, min_s}}
    of the attention kernels of all layers in one train step.  Forward: two
    products over the kept pairs; reads q (every query head), k, v (every
    key/value head: feeding each to its query heads is the implementation's
    business), writes o and the log-sum-exp.  Backward: four products (dV,
    dP, dQ, dK; re-making the scores is the implementation's choice), two
    counted with each kernel; dQ reads q k v dO and the two row statistics
    and writes dQ, dK/dV reads the same and writes dK and dV."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    tokens = batch * seq
    wide = tokens * s["heads"] * s["d"] * store       # q, o, dO, dQ: each
    narrow = tokens * s["kv"] * s["d"] * store        # k, v, dK, dV: each
    row = batch * s["heads"] * seq * F32              # lse, delta: each
    lay = s["layers"]
    flops = sum(attention_forward_flops(m, batch, seq, w)
                for w in s["windows"])
    return {
        "flash_fwd": _roof(flops, lay * (2 * wide + 2 * narrow + row),
                           peaks),
        "flash_dq": _roof(flops, lay * (3 * wide + 2 * narrow + 2 * row),
                          peaks),
        "flash_dkv": _roof(flops, lay * (2 * wide + 4 * narrow + 2 * row),
                           peaks),
    }


def gmm_required_per_step(m, batch, seq, peaks):
    """{"gmm_fwd", "gmm_dx", "gmm_dw": {flops, bytes, bound, min_s}} of the
    grouped products of all layers in one train step: gate, up and down,
    each over the expected live rows ``R = tokens k held / n``.  Forward
    reads the rows and the held experts' matrix and writes the result rows;
    ``dx`` reads the result's gradient and the matrix and writes the rows'
    gradient; ``dW`` reads both sets of rows and writes the matrix's
    gradient."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    rows = batch * seq * expected_experts_per_token(m)
    e, fe, held, lay = s["e"], s["fe"], s["held"], s["layers"]
    flops = lay * 3 * 2 * rows * e * fe          # gate, up, down
    act = lay * 3 * rows * (e + fe) * store      # a product's rows in and out
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
