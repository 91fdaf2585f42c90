"""Workload IR: a DAG of DNN layers (Stream Step 0 input).

Each layer is described by its nested-for-loop ranges (ONNX-convention dims):
  B  batch            K  output channels     C  input channels
  OY/OX output rows/cols        FY/FX filter rows/cols
plus stride / padding. This mirrors Stream's ONNX-derived layer representation
(paper Sec. III-A: "compatible with all layer types, strides, and padding
supported by ONNX").

Supported op types:
  conv    : full convolution          (loops B K C OY OX FY FX)
  dwconv  : depthwise convolution     (loops B K OY OX FY FX; C==1 per group)
  fc      : fully connected / GEMM    (loops B K C) - single-CN by topology rule
  pool    : max/avg pool              (loops B K OY OX FY FX) - SIMD-mapped
  add     : elementwise residual add  (loops B K OY OX)       - SIMD-mapped
  concat  : channel concat (zero-cost data movement, scheduling-only node)
  matmul  : activation x activation product (loops B K C OY OX), no weights:
            both operands are tensors other layers produced. Inputs play
            operand A (rows = the output rows OY, channels along C) or
            operand B (rows along the `causal` key axis, or all rows).
            Attention's scores (Q.K^T: B=heads, K=keys, C=head dim,
            OY=queries) and context (P.V: B=heads, K=head dim, C=keys,
            OY=queries) are this op.

Three optional per-layer fields describe what a layer reads, for LLM graphs
whose operands are slices, causal prefixes and routed subsets of other
layers' outputs; a layer that sets none of them is read as before:

  reads  : per input, the channel slice (lo, hi) of the producer's K axis it
           reads (None = the op's default: C channels for conv/fc, K for
           the elementwise ops, all of them for matmul).
  roles  : per input of a matmul, "a" or "b" (which operand it feeds).
  causal : the key axis ("K" or "C") of a causal layer: the CN producing
           rows [a, b) spans keys [0, b) on that axis, and reads operand B
           rows (or, where its input channels lie on the key axis, input
           channels) [0, b). The key axis has the extent of OY.
  rows   : a routed row map: the token position (in the producer's OY axis)
           of each of this layer's OY rows, strictly increasing. An MoE
           expert's layers carry their routed tokens; a layer without a
           map that reads a routed one gathers, per token row, the rows
           routed from it (the combine's scatter back).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Mapping, Sequence

# Canonical loop-dimension order used throughout Stream-core.
LOOP_DIMS = ("B", "K", "C", "OY", "OX", "FY", "FX")

# Ops whose output is spatially local in OY/OX (eligible for fused/line CNs).
SPATIAL_OPS = frozenset({"conv", "dwconv", "pool", "add", "concat"})
# Ops that require the full input fan-in for a single output (break fusion).
FULL_FANIN_OPS = frozenset({"fc"})
# Ops mapped to the SIMD core in the exploration study (pool / residual add).
SIMD_OPS = frozenset({"pool", "add", "concat"})
# Ops whose second operand is an activation, not a weight.
ACT_OPERAND_OPS = frozenset({"matmul"})


@dataclasses.dataclass(frozen=True)
class Layer:
    """One layer (node) of the workload DAG."""

    id: int
    name: str
    op: str
    dims: Mapping[str, int]  # loop dim -> extent (missing -> 1)
    stride: int = 1
    padding: int = 0
    # ids of producer layers feeding each input operand (len 1, or 2 for add)
    inputs: Sequence[int] = ()
    bits: int = 8  # operand precision (paper targets 8b edge accelerators)
    reads: Sequence | None = None           # per input: (lo, hi) or None
    roles: Sequence[str] = ()               # per matmul input: "a" | "b"
    causal: str | None = None               # key axis "K" | "C"
    rows: Sequence[int] | None = None       # routed row map (token ids)

    def d(self, name: str) -> int:
        return int(self.dims.get(name, 1))

    @property
    def mapped(self) -> bool:
        """Whether the layer sets any of `reads`, `roles`, `causal`, `rows`
        (or is a matmul): its CNs and edges then follow those fields."""
        return (self.op in ACT_OPERAND_OPS or self.reads is not None
                or bool(self.roles) or self.causal is not None
                or self.rows is not None)

    def _causal_tri(self) -> int:
        """Query-key pairs of the causal triangle: sum over query rows i of
        the i + 1 keys it attends to."""
        t = self.d("OY")
        return t * (t + 1) // 2

    # ---- derived tensor geometry -------------------------------------------------
    @property
    def out_shape(self) -> tuple[int, int, int, int]:  # (B, K, OY, OX)
        return (self.d("B"), self.d("K"), self.d("OY"), self.d("OX"))

    @property
    def in_shape(self) -> tuple[int, int, int, int]:  # (B, C, IY, IX)
        iy = (self.d("OY") - 1) * self.stride + self.d("FY") - 2 * self.padding
        ix = (self.d("OX") - 1) * self.stride + self.d("FX") - 2 * self.padding
        cin = self.d("C") if self.op not in ("dwconv", "pool", "add", "concat") else self.d("K")
        return (self.d("B"), cin, max(iy, 1), max(ix, 1))

    @property
    def macs(self) -> int:
        """Operations of the layer's equations; a causal layer counts each
        query row against the keys up to and including its own."""
        if self.op in ("add", "concat"):
            return self.d("B") * self.d("K") * self.d("OY") * self.d("OX")
        if self.causal is not None:
            rest = (x for x in LOOP_DIMS if x not in (self.causal, "OY"))
            return math.prod(self.d(x) for x in rest) * self._causal_tri()
        return math.prod(self.d(x) for x in LOOP_DIMS)

    @property
    def weight_elems(self) -> int:
        if self.op == "conv":
            return self.d("K") * self.d("C") * self.d("FY") * self.d("FX")
        if self.op == "dwconv":
            return self.d("K") * self.d("FY") * self.d("FX")
        if self.op == "fc":
            return self.d("K") * self.d("C")
        return 0

    @property
    def weight_bytes(self) -> int:
        return self.weight_elems * self.bits // 8

    @property
    def out_elems(self) -> int:
        if self.causal == "K":
            return self.d("B") * self._causal_tri() * self.d("OX")
        return math.prod(self.out_shape)

    @property
    def out_bytes(self) -> int:
        return self.out_elems * self.bits // 8


class Workload:
    """A DAG of Layers. Edges run producer -> consumer."""

    def __init__(self, name: str = "workload"):
        self.name = name
        self.layers: dict[int, Layer] = {}
        self._succ: dict[int, list[int]] = {}

    # ---- construction --------------------------------------------------------
    def add(
        self,
        name: str,
        op: str,
        dims: Mapping[str, int],
        *,
        stride: int = 1,
        padding: int = 0,
        inputs: Iterable[int] = (),
        bits: int = 8,
        reads: Iterable | None = None,
        roles: Iterable[str] = (),
        causal: str | None = None,
        rows: Iterable[int] | None = None,
    ) -> int:
        lid = len(self.layers)
        inputs = tuple(inputs)
        if reads is not None:
            reads = tuple(None if r is None else (int(r[0]), int(r[1]))
                          for r in reads)
            if len(reads) != len(inputs):
                raise ValueError(f"{name}: one `reads` entry per input")
        roles = tuple(roles)
        if op in ACT_OPERAND_OPS and (len(roles) != len(inputs) or not
                                      set(roles) <= {"a", "b"}):
            raise ValueError(f"{name}: a matmul needs a role per input")
        if causal not in (None, "K", "C"):
            raise ValueError(f"{name}: causal key axis {causal!r}")
        if rows is not None:
            rows = tuple(int(r) for r in rows)
            if len(rows) != int(dims.get("OY", 1)) or any(
                    b <= a for a, b in zip(rows, rows[1:])):
                raise ValueError(f"{name}: `rows` must give OY strictly "
                                 f"increasing token ids")
        self.layers[lid] = Layer(
            id=lid, name=name, op=op, dims=dict(dims), stride=stride,
            padding=padding, inputs=inputs, bits=bits, reads=reads,
            roles=roles, causal=causal, rows=rows,
        )
        self._succ[lid] = []
        for p in inputs:
            self._succ[p].append(lid)
        return lid

    # ---- queries -------------------------------------------------------------
    def successors(self, lid: int) -> list[int]:
        return self._succ[lid]

    def predecessors(self, lid: int) -> tuple[int, ...]:
        return tuple(self.layers[lid].inputs)

    def topo_order(self) -> list[int]:
        # layers are added in topological order by construction; verify anyway
        seen: set[int] = set()
        for lid, layer in self.layers.items():
            for p in layer.inputs:
                if p not in seen:
                    raise ValueError(f"layer {lid} consumes unseen producer {p}")
            seen.add(lid)
        return list(self.layers)

    def edges(self) -> list[tuple[int, int]]:
        return [(p, c) for c, l in self.layers.items() for p in l.inputs]

    def cache_key(self) -> tuple:
        """Content-based hashable identity (layers are mutable-by-append, so
        the key reflects the current DAG). Used to memoize CN-graph builds
        across repeated explorations of structurally identical workloads."""
        return (self.name, tuple(
            (l.id, l.op, tuple(sorted(l.dims.items())), l.stride, l.padding,
             tuple(l.inputs), l.bits) + (
                 (l.reads, l.roles, l.causal, l.rows) if l.mapped else ())
            for l in self.layers.values()))

    # ---- serialization (shard manifests ship workloads as pure data) ---------
    def to_dict(self) -> dict:
        """JSON-ready DAG description; `from_dict` round-trips it exactly
        (`cache_key()` is preserved, so content keys survive the trip)."""
        return {"name": self.name, "layers": [
            {"name": l.name, "op": l.op, "dims": dict(l.dims),
             "stride": l.stride, "padding": l.padding,
             "inputs": list(l.inputs), "bits": l.bits} | (
                 {"reads": None if l.reads is None else [
                     None if r is None else list(r) for r in l.reads],
                  "roles": list(l.roles), "causal": l.causal,
                  "rows": None if l.rows is None else list(l.rows)}
                 if l.mapped else {})
            for l in self.layers.values()]}

    @classmethod
    def from_dict(cls, data: Mapping) -> "Workload":
        """Rebuild a workload from `to_dict` output (layer ids are assigned
        in list order, matching the original append order)."""
        w = cls(str(data["name"]))
        for l in data["layers"]:
            w.add(l["name"], l["op"], {str(k): int(v)
                                       for k, v in l["dims"].items()},
                  stride=int(l["stride"]), padding=int(l["padding"]),
                  inputs=tuple(int(i) for i in l["inputs"]),
                  bits=int(l["bits"]), reads=l.get("reads"),
                  roles=l.get("roles", ()), causal=l.get("causal"),
                  rows=l.get("rows"))
        return w

    @property
    def total_macs(self) -> int:
        return sum(l.macs for l in self.layers.values())

    @property
    def total_weight_bytes(self) -> int:
        return sum(l.weight_bytes for l in self.layers.values())

    def __len__(self) -> int:
        return len(self.layers)

    def __repr__(self) -> str:  # pragma: no cover
        return f"Workload({self.name}, {len(self)} layers, {self.total_macs/1e6:.1f} MMAC)"
