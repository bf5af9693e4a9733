"""What one call of each flash-attention kernel needs, from its shapes.

Causal self-attention over (B, H, S, hd): only the S * (S + 1) / 2 pairs at
or under the diagonal count. A matmul over those pairs is 2 * pairs * hd
FLOPs per batch and head.

- ``flash_attention_fwd``: scores and values, 2 matmuls.
- ``flash_attention_bwd_dq``: scores again, dP and dQ, 3 matmuls.
- ``flash_attention_bwd_dkv``: scores again, dP, dV and dK, 4 matmuls.

The two backward kernels each rebuild the scores and dP because they are two
calls; that is what each call needs, not waste counted as work. Bytes are
each operand read once and each result written once (the least any kernel
moves): q, k, v, o and the gradients at the compute type, the row statistics
in float32.
"""

from __future__ import annotations

MATMULS = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
           "flash_attention_bwd_dkv": 4}
# (B, S, H, hd)-sized tensors read + written, and (B, H, S) float32 rows
TENSORS = {"flash_attention_fwd": (4, 1),       # q k v -> o, lse
           "flash_attention_bwd_dq": (6, 2),    # q k v o do -> dq; lse, delta
           "flash_attention_bwd_dkv": (7, 2)}   # q k v o do -> dk dv


def ops_and_bytes(kernel: str, *, batch: int, heads: int, seq: int,
                  head_dim: int, bytes_per_value: int = 2) -> tuple:
    pairs = seq * (seq + 1) // 2
    flops = MATMULS[kernel] * 2.0 * pairs * head_dim * batch * heads
    big, rows = TENSORS[kernel]
    nbytes = (big * batch * heads * seq * head_dim * bytes_per_value
              + rows * batch * heads * seq * 4)
    return flops, float(nbytes)


def calls(facts: dict) -> dict:
    """Per kernel name, the (flops, bytes) of ONE call in this cell: one call
    per layer and micro-batch, over the chip's own rows."""
    m = facts["model"]
    shape = dict(batch=facts["rows_per_chip"], heads=m["n_head"],
                 seq=facts["seq_len"], head_dim=m["n_embd"] // m["n_head"])
    return {k: ops_and_bytes(k, **shape) for k in MATMULS}
