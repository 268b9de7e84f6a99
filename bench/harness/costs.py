"""Operations and bytes of the dense decoder's work, from its shapes.

The counts the per-layer metrics divide by.  They follow the program's
own accounting (``obs/flops.op_cost`` for one GEMM, ``launch/roofline``
for a model) with two corrections: a GEMM's output is priced at the
dtype it is stored in (bfloat16, or float32 for the logits), not always
float32; and nothing here depends on the program's table of shapes.

Model work counts what the algorithm needs: causal attention over the
keys each query may see, the head only on the rows whose logits are
used, no recomputation.  A least time is the larger of operations over
peak FLOP/s and bytes over peak bytes/s.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def dims(cfg) -> dict:
    """Sizes of a program ``ArchCfg`` (dense decoder)."""
    return dict(d=cfg.d_model, h=cfg.n_heads, kv=cfg.n_kv_heads, dh=cfg.dh,
                ff=cfg.d_ff, v=cfg.vocab, layers=cfg.n_layers,
                gated=cfg.gated_mlp, tied=cfg.tie_embeddings)


def gemm(m: int, n: int, k: int, *, in_bytes: int = BF16,
         out_bytes: int = BF16) -> tuple[float, float]:
    """(FLOPs, minimal bytes) of an (m, k) x (k, n) GEMM."""
    return 2.0 * m * n * k, float((m * k + k * n) * in_bytes
                                  + m * n * out_bytes)


def least_time(flops: float, bytes_: float, peak: dict) -> float:
    return max(flops / peak["flops"], bytes_ / peak["hbm_bytes_per_s"])


def layer_gemms(m: dict, rows: int) -> list[tuple[int, int, int]]:
    """(m, n, k) of one layer's GEMMs over ``rows`` tokens."""
    d, q, kv = m["d"], m["h"] * m["dh"], m["kv"] * m["dh"]
    out = [(rows, q, d), (rows, kv, d), (rows, kv, d), (rows, d, q),
           (rows, m["ff"], d), (rows, d, m["ff"])]
    if m["gated"]:
        out.append((rows, m["ff"], d))
    return out


def forward_gemms(m: dict, rows: int, head_rows: int):
    """[(m, n, k, out_bytes)] of a forward pass: every layer over ``rows``
    tokens, the head over ``head_rows`` (float32 logits)."""
    out = [g + (BF16,) for g in layer_gemms(m, rows)] * m["layers"]
    if head_rows:
        out.append((head_rows, m["v"], m["d"], F32))
    return out


def train_gemms(m: dict, rows: int):
    """Forward GEMMs and, for each, the two of its backward pass (the
    input gradient and the weight gradient)."""
    out = []
    for a, b, c, ob in forward_gemms(m, rows, rows):
        out += [(a, b, c, ob), (a, c, b, BF16), (c, b, a, BF16)]
    return out


def gemms_cost(gemms) -> tuple[float, float]:
    f = b = 0.0
    for mm, n, k, ob in gemms:
        gf, gb = gemm(mm, n, k, out_bytes=ob)
        f += gf
        b += gb
    return f, b


def gemms_least_time(gemms, peak: dict) -> float:
    """Sum of each GEMM's own least time: they run one after another."""
    return sum(least_time(*gemm(mm, n, k, out_bytes=ob), peak)
               for mm, n, k, ob in gemms)


def attention_flops(m: dict, q_len: int, q_start: int) -> float:
    """QK^T and PV over causal keys: queries at q_start .. q_start+q_len-1
    each see the keys up to their own position, in every layer."""
    keys = q_len * q_start + q_len * (q_len + 1) / 2
    return 4.0 * m["h"] * m["dh"] * keys * m["layers"]


def weight_bytes(m: dict) -> float:
    return float(BF16 * sum(n * k for _, n, k, _ in forward_gemms(m, 0, 1)))


def kv_bytes_per_token(m: dict) -> float:
    return float(m["layers"] * 2 * m["kv"] * m["dh"] * BF16)


def decode_cost(m: dict, lengths) -> tuple[float, float]:
    """A decode step over active requests whose caches hold ``lengths``
    tokens before the step: one new token each."""
    rows = len(lengths)
    f, _ = gemms_cost(forward_gemms(m, rows, rows))
    f += sum(4.0 * m["h"] * m["dh"] * (n + 1) * m["layers"] for n in lengths)
    kvb = kv_bytes_per_token(m)
    return f, weight_bytes(m) + kvb * (sum(lengths) + rows)


def train_flops(m: dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: forward and backward (twice the
    forward) of every GEMM and of causal attention."""
    f, _ = gemms_cost(forward_gemms(m, batch * seq, batch * seq))
    return 3.0 * (f + batch * attention_flops(m, seq, 0))
