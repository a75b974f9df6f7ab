"""Operations and bytes of each kernel and step, from shapes alone, and
the census of the kernels a traced program dispatches.

The census walks the jaxpr of a program as dispatch traced it: each
``pallas_call`` is one kernel launch, multiplied by the trip counts of
the scans around it.  A launch is filed by its ``pallas_call`` name under
the kernel kind of ``launches/<kind>.py``, which reads its dims from the
operand shapes and counts its operations and bytes.  A launch of a name
that no file there gives, or of operands its kind does not know, is
filed as ``other``, with that name and the shapes.
"""

from __future__ import annotations

import collections
import dataclasses

from . import spec

__all__ = [
    "Launch",
    "census",
    "gemm_flops",
    "gemm_bytes",
    "flash_flops",
    "flash_bytes",
    "roofline_s",
    "launch_roofline_s",
    "launch_flops",
    "matmul_params",
    "prefill_flops",
    "decode_flops",
]


@dataclasses.dataclass(frozen=True)
class Launch:
    """One distinct kernel launch shape and how often a program runs it."""

    kind: str  # a file of launches/ ("gemm", "flash"), or "other"
    dims: tuple  # the kind's dims; other: (pallas_call name, operand shapes)
    dtype: str
    count: int


def _sub_jaxprs(params):
    for v in params.values():
        for x in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(x, "eqns"):
                yield x
            elif hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr


def _walk(jaxpr, mult: int, out: collections.Counter) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            kernel = eqn.params["name"]
            avals = [v.aval for v in eqn.invars]
            shapes = tuple(tuple(a.shape) for a in avals)
            # the element type of A or Q, past any scalar-prefetched index
            dt = str(next((a.dtype for a in avals if len(a.shape) >= 2), avals[0].dtype))
            kind = spec.launch_kinds().get(kernel)
            dims = spec.load_launch(kind).dims(shapes) if kind else None
            if dims is None:
                out[("other", (kernel, shapes), dt)] += mult
            else:
                out[(kind, dims, dt)] += mult
            continue
        inner = mult * int(eqn.params["length"]) if name == "scan" else mult
        for sub in _sub_jaxprs(eqn.params):
            _walk(sub, inner, out)


def census(closed_jaxpr) -> list[Launch]:
    """Kernel launches of one run of ``closed_jaxpr``
    (``jax.make_jaxpr(fn)(*args)``), largest count first."""
    c: collections.Counter = collections.Counter()
    _walk(closed_jaxpr.jaxpr, 1, c)
    return [Launch(k, d, dt, n) for (k, d, dt), n in c.most_common()]


def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}[dtype]


def gemm_flops(m: int, k: int, n: int) -> float:
    return 2.0 * m * k * n


def gemm_bytes(m: int, k: int, n: int, dtype: str = "bfloat16") -> float:
    """The least traffic: each operand read once, the result written once."""
    return float(m * k + k * n + m * n) * _itemsize(dtype)


def flash_flops(b: int, h: int, s: int, hd: int) -> float:
    """Causal self-attention: QK^T and PV over the s(s+1)/2 unmasked pairs."""
    return 4.0 * b * h * hd * s * (s + 1) / 2


def flash_bytes(b: int, h: int, kv: int, s: int, hd: int, dtype: str = "bfloat16") -> float:
    """Q and O of every head, K and V of every KV head, once each."""
    return float(2 * b * s * h * hd + 2 * b * s * kv * hd) * _itemsize(dtype)


def roofline_s(flops: float, nbytes: float, peak) -> float:
    """The least time the chip could take: bound by compute or by memory."""
    return max(flops / peak.bf16_flops, nbytes / peak.hbm_bytes_s)


def launch_flops(launch: Launch) -> float:
    """Operations of ONE launch of this shape, by its kind's file."""
    return spec.load_launch(launch.kind).flops(launch.dims)


def launch_roofline_s(launch: Launch, peak) -> float:
    """Roofline time of ONE launch of this shape."""
    kind = spec.load_launch(launch.kind)
    return roofline_s(kind.flops(launch.dims), kind.bytes(launch.dims, launch.dtype), peak)


# -- whole steps, from the configuration's stated sizes ----------------------


def matmul_params(model: dict) -> tuple[int, int]:
    """(weights multiplied per token in one layer, in the head)."""
    d, hd = model["d_model"], model["head_dim"]
    h, kv = model["n_heads"], model["n_kv_heads"]
    attn = d * (h + 2 * kv) * hd + h * hd * d
    mlp = (3 if model["mlp"] == "swiglu" else 2) * d * model["d_ff"]
    return attn + mlp, d * model["vocab_size"]


def prefill_flops(model: dict, batch: int, seq: int) -> float:
    """One prefill call: every layer's matmuls over all tokens, causal
    attention, and the head at each row's last position."""
    layer, head = matmul_params(model)
    mm = 2.0 * batch * seq * model["n_layers"] * layer + 2.0 * batch * head
    attn = model["n_layers"] * flash_flops(batch, model["n_heads"], seq, model["head_dim"])
    return mm + attn


def decode_flops(model: dict, batch: int, prompt: int, steps: int) -> float:
    """``steps`` decode steps after a ``prompt``-token prefill: the
    matmuls of every layer and the head for each token, and attention
    over the valid cache (prompt + i + 1 positions at step i)."""
    layer, head = matmul_params(model)
    per_tok = 2.0 * (model["n_layers"] * layer + head)
    ctx = sum(prompt + i + 1 for i in range(steps))
    attn = 4.0 * batch * model["n_layers"] * model["n_heads"] * model["head_dim"] * ctx
    return per_tok * batch * steps + attn
