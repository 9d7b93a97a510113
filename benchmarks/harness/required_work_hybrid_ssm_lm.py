"""Operations and bytes the hybrid state-space LM *requires*, from shapes
alone (see ``required_work.py`` for the rules: recomputation is not counted,
causal attention is counted as causal, a product of [m, k] by [k, n] is
``2 m k n`` operations).

The selective scan is counted as 7 operations a (step, channel, state):
``delta * A``, the decay's product with the state, ``delta * u``, its product
with ``B``, the sum, ``C`` times the state, the sum over states; the ``exp``
is a transcendental and is not counted, nor are ``D u`` and the gate (a few
operations a (step, channel), 1/16 of a state's).  Its backward is counted
as twice the forward, as a product's is.
"""
from . import required_work
from .required_work import BF16, F32, _roof
from .weights_hybrid_ssm_lm import sizes


def matmul_params(m):
    """Parameters that take part in matrix products: every layer's gated
    MLP, the attention layers' projections, the Mamba layers' four
    projections, and the tied head (the embedding lookup is not a product,
    the same matrix used as the head is)."""
    s = sizes(m)
    e, f, di, n, r = s["e"], s["f"], s["di"], s["n"], s["r"]
    hd = s["heads"] * s["head_dim"]
    mlp = 3 * e * f
    attn = e * (hd + 2 * s["kv_heads"] * s["head_dim"]) + hd * e
    mamba = e * 2 * di + di * (r + 2 * n) + r * di + di * e
    return (s["layers"] * mlp + s["n_attn"] * attn + s["n_mamba"] * mamba
            + e * s["v"])


# QK^T and PV of one attention layer over the causal half, every query head
# counted (key/value heads are shared, their products are not)
attention_forward_flops = required_work.attention_forward_flops


def scan_forward_flops(m, batch, seq):
    """One Mamba layer's selective scan, forward."""
    s = sizes(m)
    return 7 * batch * seq * s["di"] * s["n"]


def train_flops_per_step(m, batch, seq):
    """Forward plus backward (the backward counted as two forwards)."""
    s = sizes(m)
    tokens = batch * seq
    fwd = (2 * tokens * matmul_params(m)
           + s["n_attn"] * attention_forward_flops(m, batch, seq)
           + s["n_mamba"] * scan_forward_flops(m, batch, seq))
    return 3 * fwd


def scan_required_per_step(m, batch, seq, peaks):
    """{"ssm_scan_fwd", "ssm_scan_bwd": {flops, bytes, bound, min_s}} over
    the Mamba layers of one train step.  Forward: reads ``u``, ``z``
    (model type), ``delta`` (float32) and writes ``y`` once, plus ``B``,
    ``C``, ``A``, ``D``.  Backward: reads those and ``dy`` and writes the
    gradients of ``u``, ``z``, ``delta`` once, plus those of ``B``, ``C``,
    ``A``, ``D``.  Keeping or re-making the states is the implementation's
    choice and is not counted."""
    s = sizes(m)
    store = BF16 if m["dtype"] == "bfloat16" else F32
    wide = batch * seq * s["di"]
    narrow = 2 * batch * seq * s["n"] * store + s["di"] * (s["n"] + 1) * F32
    fwd_bytes = wide * (3 * store + F32) + narrow
    bwd_bytes = wide * (3 * store + F32) + wide * (3 * store + F32) + 2 * narrow
    flops = scan_forward_flops(m, batch, seq)
    lm = s["n_mamba"]
    return {"ssm_scan_fwd": _roof(lm * flops, lm * fwd_bytes, peaks),
            "ssm_scan_bwd": _roof(lm * 2 * flops, lm * bwd_bytes, peaks)}


def pallas_required_per_step(m, batch, seq, peaks):
    """Every Pallas kernel family on the step's path: flash forward and
    backward in the attention layers, rmsnorm twice a layer and once before
    the head (the mixers' three inner norms are plain ``lax``), softmax
    cross-entropy over float32 logits, the selective scan."""
    s = sizes(m)
    e, vocab, tokens = s["e"], s["v"], batch * seq
    store = BF16 if m["dtype"] == "bfloat16" else F32
    act = tokens * e * store
    la = s["n_attn"]
    att_fwd = attention_forward_flops(m, batch, seq)
    lse = batch * s["heads"] * seq * F32
    out = {
        "flash_fwd": _roof(la * att_fwd, la * (4 * act + lse), peaks),
        "flash_bwd": _roof(la * 2 * att_fwd, la * (8 * act + lse), peaks),
    }
    n_norm = 2 * s["layers"] + 1
    out["rmsnorm_fwd"] = _roof(n_norm * 4 * tokens * e, n_norm * 2 * act,
                               peaks)
    out["rmsnorm_bwd"] = _roof(n_norm * 8 * tokens * e, n_norm * 3 * act,
                               peaks)
    logits = tokens * vocab * F32
    out["xent_fwd"] = _roof(4 * tokens * vocab, logits, peaks)
    out["xent_bwd"] = _roof(4 * tokens * vocab, 2 * logits, peaks)
    out.update(scan_required_per_step(m, batch, seq, peaks))
    return out
