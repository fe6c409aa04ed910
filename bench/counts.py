"""Operations and bytes that the benchmarked work needs, from its shapes.

Counts are of the work the algorithm needs, not of what the program
happens to run: causal attention counts the keys at or before each query,
the vocabulary is the true one (not the padded one), and recomputation
under rematerialisation is not counted.  A multiply-add is two operations.
"""

from __future__ import annotations

from bench.weights import Dims

BF16, F32 = 2, 4


def layer_matmul_params(dm: Dims) -> int:
    """Weights one token multiplies in one layer (q, k, v, o, gate, up,
    down)."""
    d, hk, nk = dm.d_model, dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    return d * hk + 2 * d * nk + hk * d + 3 * d * dm.d_ff


def token_matmul_flops(dm: Dims) -> int:
    """Forward matmul operations of one token: every layer plus the head."""
    return 2 * (dm.layers * layer_matmul_params(dm) + dm.d_model * dm.vocab)


def attn_flops(dm: Dims, keys: int) -> int:
    """Forward attention operations of one query over ``keys`` keys, all
    layers: q.k and p.v, each 2 * heads * head_dim per key."""
    return dm.layers * 4 * dm.heads * dm.head_dim * keys


def seq_forward_flops(dm: Dims, seq: int) -> int:
    """Forward operations of one causal sequence of ``seq`` tokens."""
    return (seq * token_matmul_flops(dm)
            + attn_flops(dm, seq * (seq + 1) // 2))


def p2_step_flops(dm: Dims, batch: int, seq: int) -> int:
    """One buffered-KD step: the student's forward and backward (three
    forwards' worth) plus the teacher's and the buffer's forwards."""
    return 5 * batch * seq_forward_flops(dm, seq)


def decode_tick_flops(dm: Dims, keys) -> int:
    """One decode tick: one token for each active row, row i attending
    ``keys[i]`` positions (its own included)."""
    return sum(token_matmul_flops(dm) + attn_flops(dm, k) for k in keys)


def paged_decode_call(dm: Dims, keys, page_size: int):
    """(operations, bytes) of one layer's paged decode attention over the
    active rows, row i attending ``keys[i]`` positions: q.k and p.v, and
    the K and V pages those positions lie in, plus q and the output."""
    nd = dm.kv_heads * dm.head_dim
    flops = sum(4 * dm.heads * dm.head_dim * k for k in keys)
    pages = sum(-(-k // page_size) for k in keys)
    byts = (2 * pages * page_size * nd * BF16
            + 2 * len(keys) * dm.heads * dm.head_dim * BF16)
    return flops, byts


# Elementwise operations per logit of the fused buffered-KD loss: scaling,
# max, exp and sums for the student's CE and tempered softmax, and the
# teacher's and buffer's tempered softmax and KL terms.
KD_FWD_OPS_PER_LOGIT = 16
KD_BWD_OPS_PER_LOGIT = 12


def kd_loss_fwd_call(dm: Dims, rows: int):
    """(operations, bytes) of one forward kd_loss call: read the student,
    teacher and buffer logits (f32) once."""
    n = rows * dm.vocab
    return KD_FWD_OPS_PER_LOGIT * n, 3 * n * F32


def kd_loss_bwd_call(dm: Dims, rows: int):
    """(operations, bytes) of one backward kd_loss call: read the three
    logit tensors and write the student's gradient (f32)."""
    n = rows * dm.vocab
    return KD_BWD_OPS_PER_LOGIT * n, 4 * n * F32


def roofline_s(flops: float, byts: float, peak_flops: float,
               peak_bw: float) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, byts / peak_bw)
