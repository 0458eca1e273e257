"""The operators of one forward pass of a dense GQA language model, from its
configuration file and a traffic mix.

This is the paper's evaluation granularity (one entry per projection
family, with repeat counts for the layer stack), kept with the benchmark so
that the yardstick cannot drift with the program's own operator lists. The
traffic names the sequence length, the projection kind (``qmatmul`` for the
paper's int8 setting) and which operator families the cell tunes.
"""

from __future__ import annotations

import dataclasses

from bench.lib import costs


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # stable name: the benchmark's named scope is bench.op.<name>
    kind: str  # qmatmul | matmul | attention
    dims: tuple[int, ...]
    dtype: str
    count: int

    def cost(self) -> costs.Cost:
        if self.kind == "qmatmul":
            return costs.qmatmul(*self.dims)
        if self.kind == "matmul":
            return costs.matmul(*self.dims, self.dtype)
        if self.kind == "attention":
            return costs.attention(*self.dims, self.dtype, causal=True)
        raise ValueError(f"unknown operator kind {self.kind!r}")


def forward_ops(cfg: dict, traffic: dict) -> list[Op]:
    """Operators of one forward pass over ``traffic["seq"]`` tokens, merged
    by shape (a shape that two projection families share is one operator
    whose count is their sum), in first-seen order, restricted to the
    families ``traffic["families"]`` lists."""
    s = traffic["seq"]
    d, f, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    hq, hkv, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    layers = cfg["n_layers"]
    kind = traffic["projection"]
    pdt = cfg["projection_dtype"]

    def proj(name, count, n, k):
        return (name, kind, (s, n, k), pdt, count)

    every = [
        proj("q", layers, hq * hd, d),
        proj("kv", 2 * layers, hkv * hd, d),
        ("attention", "attention", (1, hq, hkv, s, s, hd),
         cfg["attention_dtype"], layers),
        proj("o", layers, d, hq * hd),
        proj("gate_up", 2 * layers, f, d),
        proj("down", layers, d, f),
        proj("lm_head", 1, v, d),
    ]
    wanted = set(traffic["families"])
    unknown = wanted - {e[0] for e in every}
    if unknown:
        raise ValueError(f"unknown operator families {sorted(unknown)}")
    merged: dict[tuple, list] = {}
    for name, k, dims, dtype, count in every:
        if name not in wanted:
            continue
        key = (k, dims, dtype)
        if key in merged:
            merged[key][0] += "+" + name
            merged[key][1] += count
        else:
            merged[key] = [name, count]
    return [Op(name, k, dims, dtype, count)
            for (k, dims, dtype), (name, count) in merged.items()]
