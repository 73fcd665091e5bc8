"""Operations and bytes of the work the engine was asked to do, counted
from the model's shapes and each request's context: the logical work, not
the pages, padding or query rows a kernel happens to walk. So a roofline
share reads the same work whatever implements it, and cannot pass 100%
unless the kernel beats the chip.

``m`` is a :class:`Shapes` (widths of one dense decoder) throughout.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Shapes:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated_mlp: bool = True      # SwiGLU: three d x d_ff matrices
    bytes_per_el: int = 2       # bf16 weights, activations and KV


def layer_matmul_flops(m: Shapes) -> int:
    """FLOPs of one token through one layer's matrices (q, k, v, o, MLP)."""
    attn = m.d_model * (m.n_heads + 2 * m.n_kv_heads) * m.head_dim
    attn += m.n_heads * m.head_dim * m.d_model
    mlp = (3 if m.gated_mlp else 2) * m.d_model * m.d_ff
    return 2 * (attn + mlp)


def attention_flops(m: Shapes, ctx_sum: int) -> int:
    """Scores and weighted sum over all layers, for queries whose contexts
    add up to ``ctx_sum`` tokens: 2 FLOPs per multiply-add, twice (QK, PV)."""
    return 4 * m.n_layers * m.n_heads * m.head_dim * ctx_sum


def head_flops(m: Shapes, rows: int) -> int:
    return 2 * m.d_model * m.vocab * rows


def kv_bytes_per_token_layer(m: Shapes) -> int:
    """K and V of one token in one layer."""
    return 2 * m.n_kv_heads * m.head_dim * m.bytes_per_el


@dataclasses.dataclass
class Work:
    """Work of one segment (or a sum of segments)."""
    rows: int = 0            # tokens through the layer stack
    logit_rows: int = 0      # tokens whose logits were needed
    ctx_sum: int = 0         # sum over rows of the context each attended
    read_flops: int = 0      # read kernel, all layers
    read_bytes: int = 0

    def add(self, other: "Work") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))

    def model_flops(self, m: Shapes) -> int:
        return (self.rows * m.n_layers * layer_matmul_flops(m)
                + attention_flops(m, self.ctx_sum)
                + head_flops(m, self.logit_rows))


def read_call(m: Shapes, first: int, n_queries: int) -> tuple:
    """(flops, bytes) of the read kernel, all layers, for one slot in one
    step: ``n_queries`` consecutive queries at positions ``first`` ..
    ``first + n_queries - 1``, each attending every earlier position and
    itself. K and V of the slot's context are read once; q and the output
    are read and written once."""
    last_ctx = first + n_queries
    ctx_sum = n_queries * first + n_queries * (n_queries + 1) // 2
    flops = attention_flops(m, ctx_sum)
    qo = 2 * n_queries * m.n_heads * m.head_dim * m.bytes_per_el
    kv = last_ctx * kv_bytes_per_token_layer(m)
    return flops, m.n_layers * (qo + kv)


def prefill_steps(m: Shapes, start: int, end: int, chunk: int) -> Work:
    """Prompt rows ``start`` .. ``end - 1`` in chunks of ``chunk`` rows from
    ``start``, one chunk a step."""
    w = Work()
    for a in range(start, end, chunk):
        n = min(chunk, end - a)
        f, b = read_call(m, a, n)
        w.rows += n
        w.ctx_sum += n * a + n * (n + 1) // 2
        w.read_flops += f
        w.read_bytes += b
    return w


def decode_steps(m: Shapes, first: int, n: int) -> Work:
    """``n`` decode steps processing the tokens at positions ``first`` ..
    ``first + n - 1``, each emitting the next token."""
    w = Work(logit_rows=n)
    for p in range(first, first + n):
        f, b = read_call(m, p, 1)
        w.rows += 1
        w.ctx_sum += p + 1
        w.read_flops += f
        w.read_bytes += b
    return w
