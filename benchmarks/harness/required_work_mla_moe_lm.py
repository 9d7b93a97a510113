"""Operations and bytes the latent-attention, routed-experts LM *requires*,
from shapes alone (see ``required_work.py`` for the rules: recomputation is
not counted, causal attention is counted as causal, a product of [m, k] by
[k, n] is ``2 m k n`` operations).

**The routed experts are counted at their expectation**: a token's ``k``
slots fall on the experts held here with probability ``held / n`` each, so
it meets ``k held / n`` routed experts (6 x 32 / 64 = 3 in the
DeepSeek-V2-Lite cut) whatever a step's routing really was; the run prints
the share that landed beside its comparison.  Attention is counted at its
two widths: the scores over ``dn + dr``, the values over ``dv``.
"""
from .required_work import BF16, F32, _roof
from .weights_mla_moe_lm import sizes


def expected_experts_per_token(m):
    s = sizes(m)
    return s["k"] * s["held"] / s["n"]


def mla_params(m):
    """One layer's attention matrices: wq, wkv_a, wkv_b, wo."""
    s = sizes(m)
    e, h = s["e"], s["heads"]
    return (e * h * (s["dn"] + s["dr"]) + e * (s["r"] + s["dr"])
            + s["r"] * h * (s["dn"] + s["dv"]) + h * s["dv"] * e)


def matmul_params_per_token(m):
    """Parameters a token meets in matrix products, forward: every layer's
    attention matrices, the dense layers' MLP, an expert layer's router,
    shared expert and the *expected* routed experts, and the head (the
    embedding is a lookup)."""
    s = sizes(m)
    e = s["e"]
    dense = 3 * e * s["f"]
    moe = (e * s["n"] + 3 * e * s["fs"]
           + expected_experts_per_token(m) * 3 * e * s["fe"])
    return (s["layers"] * mla_params(m) + s["n_dense"] * dense
            + s["n_moe"] * moe + e * s["v"])


def attention_forward_flops(m, batch, seq):
    """QK^T over ``dn + dr`` and PV over ``dv`` of one layer, every head,
    over the causal half: query t sees t + 1 keys."""
    s = sizes(m)
    pairs = batch * seq * (seq + 1) // 2
    return 2 * pairs * s["heads"] * (s["dn"] + s["dr"] + s["dv"])


def train_flops_per_step(m, batch, seq):
    """Forward plus backward (the backward counted as two forwards)."""
    s = sizes(m)
    fwd = (2 * batch * seq * matmul_params_per_token(m)
           + s["layers"] * attention_forward_flops(m, batch, seq))
    return 3 * fwd


def gmm_required_per_step(m, batch, seq, peaks):
    """{"gmm_fwd", "gmm_dx", "gmm_dw": {flops, bytes, bound, min_s}} of the
    grouped products of all expert layers in one train step: gate, up and
    down, each over the expected live rows ``R = tokens k held / n``.
    Forward reads the rows and the held experts' matrix and writes the
    result rows; ``dx`` reads the result's gradient and the matrix and
    writes the rows' gradient; ``dW`` reads both sets of rows and writes the
    matrix's gradient."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    rows = batch * seq * expected_experts_per_token(m)
    e, fe, held, lm = s["e"], s["fe"], s["held"], s["n_moe"]
    flops = lm * 3 * 2 * rows * e * fe          # gate, up, down
    act = lm * 3 * rows * (e + fe) * store      # a product's rows in and out
    mats = lm * 3 * held * e * fe * store
    return {"gmm_fwd": _roof(flops, act + mats, peaks),
            "gmm_dx": _roof(flops, act + mats, peaks),
            "gmm_dw": _roof(flops, act + mats, peaks)}


def pallas_required_per_step(m, batch, seq, peaks):
    """Every Pallas kernel family on the step's path: flash forward and
    backward at the two widths, rmsnorm twice a layer, once on the latent
    and once before the head, softmax cross-entropy over float32 logits,
    the grouped products."""
    s = sizes(m)
    e, vocab, tokens, lay = s["e"], s["v"], batch * seq, s["layers"]
    store = BF16 if m["dtype"] == "bfloat16" else F32
    h = s["heads"]
    qk = tokens * h * (s["dn"] + s["dr"]) * store     # q, k, dq, dk: each
    vo = tokens * h * s["dv"] * store                 # v, o, do, dv: each
    lse = batch * h * seq * F32
    att_fwd = attention_forward_flops(m, batch, seq)
    out = {
        # reads q k v, writes o and the log-sum-exp
        "flash_fwd": _roof(lay * att_fwd, lay * (2 * qk + 2 * vo + lse),
                           peaks),
        # reads q k v o dO, writes dq dk dv
        "flash_bwd": _roof(lay * 2 * att_fwd,
                           lay * (4 * qk + 4 * vo + lse), peaks),
    }
    wide = (2 * lay + 1) * tokens * e + lay * tokens * s["r"]   # elements
    out["rmsnorm_fwd"] = _roof(4 * wide, 2 * wide * store, peaks)
    out["rmsnorm_bwd"] = _roof(8 * wide, 3 * wide * store, peaks)
    logits = tokens * vocab * F32
    out["xent_fwd"] = _roof(4 * tokens * vocab, logits, peaks)
    out["xent_bwd"] = _roof(4 * tokens * vocab, 2 * logits, peaks)
    out.update(gmm_required_per_step(m, batch, seq, peaks))
    return out
