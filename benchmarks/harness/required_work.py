"""Operations and bytes a piece of work *requires*, from shapes alone.

Every share of a peak or of a roofline the benchmark reports divides one of
these by a measured time.  They count what the mathematics needs, not what an
implementation does: recomputation (remat, a flash backward's second pass
over the scores) is not counted, causal attention is counted as causal, and a
decode step needs the live keys and values, not ``max_seq_len`` of them.  So
no share can pass 100 % unless a time leaves out part of the work.

A matrix product of [m, k] by [k, n] is ``2 m k n`` operations.
"""

BF16 = 2
F32 = 4


# ---------------------------------------------------------------------------
# transformer LM (this repo's block: RMSNorm, fused QKV, MHA, GELU MLP)
# ---------------------------------------------------------------------------
def lm_matmul_params(cfg):
    """Parameters that take part in matrix products: the blocks' four
    matrices and the output head (the embedding is a lookup)."""
    e, f, layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    per_layer = e * 3 * e + e * e + 2 * e * f
    return layers * per_layer + e * cfg["vocab_size"]


def lm_param_count(cfg):
    e, layers = cfg["d_model"], cfg["n_layers"]
    return (lm_matmul_params(cfg) + cfg["vocab_size"] * e
            + layers * 2 * e + e)


def lm_layer_forward_flops(cfg, batch, seq):
    """One block, forward, causal attention counted as causal."""
    e, f = cfg["d_model"], cfg["d_ff"]
    tokens = batch * seq
    dense = 2 * tokens * (e * 3 * e + e * e + 2 * e * f)
    return dense + attention_forward_flops(cfg, batch, seq)


def attention_forward_flops(cfg, batch, seq):
    """QK^T and PV over the causal half: query t sees t + 1 keys."""
    e = cfg["d_model"]               # heads * head_dim
    pairs = batch * seq * (seq + 1) // 2
    return 2 * 2 * pairs * e


def lm_head_forward_flops(cfg, batch, seq):
    return 2 * batch * seq * cfg["d_model"] * cfg["vocab_size"]


def lm_train_flops_per_step(cfg, batch, seq):
    """Forward plus backward: the backward of a product costs two products."""
    fwd = (cfg["n_layers"] * lm_layer_forward_flops(cfg, batch, seq)
           + lm_head_forward_flops(cfg, batch, seq))
    return 3 * fwd


def lm_serve_flops(cfg, tokens):
    """2 x (parameters in products) x tokens processed (prompt and output).
    Attention over the context is left out, so this is a floor."""
    return 2 * lm_matmul_params(cfg) * tokens


# ---------------------------------------------------------------------------
# the Pallas kernels on the LM train step's path
# ---------------------------------------------------------------------------
def _roof(flops, nbytes, peaks):
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "bound": "flops" if t_flops >= t_bytes else "hbm",
            "min_s": max(t_flops, t_bytes)}


def pallas_required_per_step(cfg, batch, seq, peaks):
    """{kernel: {flops, bytes, bound, min_s}} for one train step: what each
    kernel family on the step's path has to do at least, whatever runs it."""
    e, layers, vocab = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    heads = cfg["n_heads"]
    tokens = batch * seq
    act = tokens * e * BF16                         # one [B, T, E] in bf16
    att_fwd = attention_forward_flops(cfg, batch, seq)
    lse = batch * heads * seq * F32
    out = {}
    # forward: two products; reads q, k, v, writes o and the log-sum-exp
    out["flash_fwd"] = _roof(layers * att_fwd,
                             layers * (4 * act + lse), peaks)
    # backward needs four products (dV, dP, dQ, dK); re-making the scores is
    # the implementation's choice.  dQ: reads q k v o dO lse, writes dQ;
    # dK/dV: reads the same, writes dK and dV.  Counted together as reading
    # q k v o dO once and writing dq dk dv.
    out["flash_bwd"] = _roof(layers * 2 * att_fwd,
                             layers * (8 * act + lse), peaks)
    # rmsnorm: twice a block and once before the head.  Forward reads x and
    # writes y; backward reads x and dy and writes dx.  ~4 operations an
    # element either way: far under the bandwidth bound.
    n_norm = 2 * layers + 1
    out["rmsnorm_fwd"] = _roof(n_norm * 4 * tokens * e,
                               n_norm * 2 * act, peaks)
    out["rmsnorm_bwd"] = _roof(n_norm * 8 * tokens * e,
                               n_norm * 3 * act, peaks)
    # softmax cross-entropy over f32 logits: forward reads them once;
    # backward reads them and writes their gradient.
    logits = tokens * vocab * F32
    out["xent_fwd"] = _roof(4 * tokens * vocab, logits, peaks)
    out["xent_bwd"] = _roof(4 * tokens * vocab, 2 * logits, peaks)
    return out


# ---------------------------------------------------------------------------
# one decode iteration
# ---------------------------------------------------------------------------
def decode_required_bytes(cfg, live_lens):
    """Bytes one decode iteration has to move: every weight that takes part
    in a product once, one embedding row a sequence, and the live keys and
    values of the active sequences (``live_lens``: tokens held by each)."""
    e, layers = cfg["d_model"], cfg["n_layers"]
    weights = (lm_matmul_params(cfg) + layers * 2 * e + e) * BF16
    rows = len(live_lens) * e * BF16
    kv = 2 * layers * sum(live_lens) * e * BF16
    return weights + rows + kv


# ---------------------------------------------------------------------------
# ResNet-50 v1 as the model zoo builds it (stride on a block's first 1x1)
# ---------------------------------------------------------------------------
def resnet50_v1_convs(size=224, classes=1000):
    """[(name, c_in, c_out, kernel, out_h)] of every convolution and the
    classifier (kernel 0), for a ``size`` x ``size`` image."""
    convs = []
    h = (size + 2 * 3 - 7) // 2 + 1                 # 7x7 / 2, pad 3
    convs.append(("stem", 3, 64, 7, h))
    h = (h + 2 * 1 - 3) // 2 + 1                    # max-pool 3x3 / 2, pad 1
    c_in = 64
    for stage, (blocks, c_out) in enumerate(
            ((3, 256), (4, 512), (6, 1024), (3, 2048)), start=1):
        mid = c_out // 4
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 1) else 1
            h_out = (h - 1) // stride + 1
            tag = "stage%d.%d" % (stage, b)
            convs.append((tag + ".conv1", c_in, mid, 1, h_out))
            convs.append((tag + ".conv2", mid, mid, 3, h_out))
            convs.append((tag + ".conv3", mid, c_out, 1, h_out))
            if b == 0:
                convs.append((tag + ".down", c_in, c_out, 1, h_out))
            c_in, h = c_out, h_out
    convs.append(("fc", c_in, classes, 0, 1))
    return convs


def resnet50_forward_flops_per_image(size=224, classes=1000):
    total = 0
    for _name, c_in, c_out, k, h in resnet50_v1_convs(size, classes):
        total += 2 * c_in * c_out * max(k, 1) ** 2 * h * h
    return total


def resnet50_train_flops_per_image(size=224, classes=1000):
    return 3 * resnet50_forward_flops_per_image(size, classes)
