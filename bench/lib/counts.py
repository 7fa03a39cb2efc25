"""Operation and byte counts computed from shapes: the model FLOPs of a
train step (the MFU numerator), the work of each kernel (roofline
numerators) and the work of each serving call.

``train_step_flops`` is the benchmark's copy of the program's
``costmodel.train_step_flops`` for the dense family, computed from the
configuration's sizes rather than from the program's parameter specs, so
that no change to the program can move the numerator.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dense:
    """The sizes of a dense decoder-only transformer, from a configuration
    file under ``bench/configs``."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    act: str = "gelu"
    tie_embeddings: bool = False

    @classmethod
    def from_config(cls, cfg: dict) -> "Dense":
        if cfg.get("family", "dense") != "dense":
            raise ValueError(f"counts.Dense covers the dense family, not "
                             f"{cfg['family']!r}")
        return cls(n_layers=cfg["n_layers"], d_model=cfg["d_model"],
                   n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
                   head_dim=cfg["head_dim"], d_ff=cfg["d_ff"],
                   vocab_size=cfg["vocab_size"], act=cfg.get("act", "gelu"),
                   tie_embeddings=cfg.get("tie_embeddings", False))

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = 2 * d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
        mlp = (3 if self.act == "swiglu" else 2) * d * self.d_ff
        return attn + mlp

    @property
    def matmul_params(self) -> int:
        """Every >= 2-D weight a token multiplies: the layers and the output
        head (the embedding lookup is a gather)."""
        return (self.n_layers * self.layer_matmul_params
                + self.d_model * self.vocab_size)

    @property
    def params(self) -> int:
        """All parameters: matmul weights, the embedding (when untied) and
        the LayerNorm scales and biases."""
        norms = (2 * self.n_layers + 1) * 2 * self.d_model
        embed = 0 if self.tie_embeddings else self.vocab_size * self.d_model
        return self.matmul_params + embed + norms

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        return 2 * self.n_layers * self.n_kv_heads * self.head_dim * itemsize


def train_step_flops(m: Dense, global_batch: int, seq_len: int) -> float:
    """Model FLOPs of one optimizer step over the whole job: 6 per matmul
    parameter per token, plus the attention quadratic 3 x 4 s^2 h hd per
    layer and sequence (full square, as the program's cost model counts
    it).  Recomputation is not counted."""
    tokens = global_batch * seq_len
    matmul = 6.0 * m.matmul_params * tokens
    attn = 3.0 * 4.0 * global_batch * m.n_heads * m.head_dim * (
        m.n_layers * seq_len * seq_len)
    return matmul + attn


# ---------------------------------------------------------------------------
# Kernels.  Work is counted over the causal half (query-key pairs with
# k <= q), so that a kernel that skips masked blocks cannot read over its
# roofline; bytes are each operand read once and each result written once.
# ---------------------------------------------------------------------------

def _pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def flash_fwd(batch: int, heads: int, seq: int, hd: int,
              itemsize: int = 2) -> tuple[float, float]:
    """Causal flash-attention forward on (batch, heads, seq, hd): QK^T and
    PV.  Reads q, k, v; writes o and the fp32 log-sum-exp."""
    flops = 4.0 * batch * heads * _pairs(seq) * hd
    nbytes = 4.0 * batch * heads * seq * hd * itemsize + 4.0 * batch * heads * seq
    return flops, nbytes


def flash_bwd_dq(batch: int, heads: int, seq: int, hd: int,
                 itemsize: int = 2) -> tuple[float, float]:
    """The dq kernel: recomputes QK^T, then dO V^T and dS K.  Reads q, k,
    v, dO and two fp32 row vectors; writes dq."""
    flops = 6.0 * batch * heads * _pairs(seq) * hd
    nbytes = (5.0 * batch * heads * seq * hd * itemsize
              + 8.0 * batch * heads * seq)
    return flops, nbytes


def flash_bwd_dkv(batch: int, heads: int, seq: int, hd: int,
                  itemsize: int = 2) -> tuple[float, float]:
    """The dk/dv kernel: recomputes QK^T, then P^T dO, dO V^T and dS^T Q.
    Reads q, k, v, dO and two fp32 row vectors; writes dk and dv."""
    flops = 8.0 * batch * heads * _pairs(seq) * hd
    nbytes = (6.0 * batch * heads * seq * hd * itemsize
              + 8.0 * batch * heads * seq)
    return flops, nbytes


def gelu_mlp_in(rows: int, d: int, f: int,
                itemsize: int = 2) -> tuple[float, float]:
    """Fused gelu(x @ w1) on (rows, d) x (d, f): reads x and w1, writes the
    (rows, f) activation.  The elementwise GELU is not counted."""
    flops = 2.0 * rows * d * f
    nbytes = float(itemsize) * (rows * d + d * f + rows * f)
    return flops, nbytes


# ---------------------------------------------------------------------------
# Serving calls: the work a prefill or a decode tick requires.
# ---------------------------------------------------------------------------

def prefill_work(m: Dense, length: int,
                 itemsize: int = 2) -> tuple[float, float]:
    """One prompt of ``length`` tokens: every matmul weight once per token,
    causal attention, and the output head for the last token only.  Bytes:
    the weights once and the prompt's KV written."""
    layer = 2.0 * m.n_layers * m.layer_matmul_params * length
    attn = 4.0 * m.n_layers * m.n_heads * m.head_dim * _pairs(length)
    head = 2.0 * m.d_model * m.vocab_size
    weights = itemsize * m.matmul_params
    kv = m.kv_bytes_per_token(itemsize) * length
    return layer + attn + head, float(weights + kv)


def decode_work(m: Dense, n_active: int, kv_tokens: int,
                itemsize: int = 2) -> tuple[float, float]:
    """One decode tick of ``n_active`` sequences that attend over
    ``kv_tokens`` cached positions in all: every matmul weight once per
    sequence and attention over the live cache.  Bytes: the weights once
    and the live KV of the active slots (not the whole block table)."""
    flops = (2.0 * m.matmul_params * n_active
             + 4.0 * m.n_layers * m.n_heads * m.head_dim * kv_tokens)
    nbytes = itemsize * m.matmul_params + m.kv_bytes_per_token(itemsize) * (
        kv_tokens + n_active)
    return flops, float(nbytes)
