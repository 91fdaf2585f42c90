"""Stream Step 1: Computation-Node identification & attribute extraction.

A CN isolates a subset of inner for-loops of a layer; the remaining outer-CN
loops enumerate the CNs and fix their intra-layer execution order (paper
Sec. III-A). Identification follows the paper's two principles:

1. *Layer topology awareness* — full-fan-in layers (fc) collapse to a single
   CN (breaking the fused stack); spatially-local layers (conv/pool/add/...)
   split along their spatial output loops (OY, optionally OX).

2. *HW dataflow awareness* — a CN must minimally encompass every loop dim
   that is spatially unrolled in ANY core of the accelerator, so no split is
   made along such dims (or tiles are kept >= the max unroll factor).

Per-CN attributes (paper Fig. 5):
  - `discardable_inputs`: input elements used exclusively by this CN, freed
    when it finishes (exact half-space intersection math, see
    `_exclusive_volume`),
  - `new_outputs`: final output elements first produced by this CN.

Layers that say what they read (`Layer.mapped`: channel slices, matmul
operand roles, a causal key axis, routed row maps) split along their token
rows OY only and take their input regions from those fields
(`_mapped_cns`): a causal CN producing rows [a, b) spans keys [0, b), so
the CNs of a causal layer tile its causal extent exactly at band
granularity; a routed layer's CN reads the producer rows of its own routed
tokens. Every other layer is split as above, unchanged.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Mapping, Sequence

import numpy as np

from repro.core.workload import (ACT_OPERAND_OPS, FULL_FANIN_OPS, Layer,
                                 Workload)

# Dims along which CNs may be split (spatial output dims, non-reduction).
SPLITTABLE = ("OY", "OX")


@dataclasses.dataclass(frozen=True)
class Rect:
    """Axis-aligned integer box: dim -> (start, stop). Missing dim == full."""

    ranges: tuple[tuple[str, int, int], ...]

    def volume(self) -> int:
        return math.prod(max(0, b - a) for _, a, b in self.ranges)

    def as_dict(self) -> dict[str, tuple[int, int]]:
        return {d: (a, b) for d, a, b in self.ranges}

    def intersection_volume(self, other: "Rect") -> int:
        mine, theirs = self.as_dict(), other.as_dict()
        vol = 1
        for d in set(mine) | set(theirs):
            a0, b0 = mine.get(d, (-(1 << 60), 1 << 60))
            a1, b1 = theirs.get(d, (-(1 << 60), 1 << 60))
            vol *= max(0, min(b0, b1) - max(a0, a1))
            if vol == 0:
                return 0
        return vol


@dataclasses.dataclass
class CN:
    """A computation node: one schedulable part of a layer."""

    id: int                      # global CN id
    layer: int                   # owning layer id
    idx: tuple[int, ...]         # position in the outer-CN loop grid
    intra_rank: int              # row-major rank == intra-layer exec order
    out_rect: Rect               # produced region of the layer output tensor
    in_rects: dict[int, Rect]    # producer layer id (-1 = external) -> needed input region
    macs: int
    discardable_inputs: int      # elements freed when this CN finishes
    new_inputs: int              # input elements not already needed by earlier CNs
    new_outputs: int             # final output elements generated
    weight_bytes: int            # layer weights (shared across the layer's CNs)
    in_bits: int = 8
    out_bits: int = 8
    # per-CN reduction extents that replace the layer's (a causal layer
    # whose keys lie on C reduces over its band's key prefix only)
    reduce: tuple[tuple[str, int], ...] = ()

    @property
    def out_bytes(self) -> int:
        return self.new_outputs * self.out_bits // 8

    def size_signature(self) -> tuple:
        """CNs with equal signatures have identical mapping cost (Step 3 cache key).

        Keyed on loop EXTENTS, not absolute ranges: the intra-core mapping
        cost only sees `stop - start` per dim, so e.g. all interior row-bands
        of a layer collapse to one signature and are costed once. Memoized —
        every engine build over a cached graph re-reads it per CN.
        """
        sig = getattr(self, "_sig", None)
        if sig is None:
            sig = (self.layer, tuple(sorted(
                (d, b - a) for d, a, b in self.out_rect.ranges)))
            if self.reduce:
                sig += (self.reduce,)
            self._sig = sig
        return sig


def _split_ranges(extent: int, parts: int) -> list[tuple[int, int]]:
    """Split [0, extent) into `parts` near-equal contiguous ranges."""
    parts = max(1, min(parts, extent))
    base, rem = divmod(extent, parts)
    out, start = [], 0
    for i in range(parts):
        stop = start + base + (1 if i < rem else 0)
        out.append((start, stop))
        start = stop
    return out


def _receptive(rng: tuple[int, int], stride: int, fsize: int, pad: int, in_extent: int) -> tuple[int, int]:
    """Input range needed to produce output range `rng` (clipped by padding)."""
    a = rng[0] * stride - pad
    b = (rng[1] - 1) * stride - pad + fsize
    return (max(0, a), min(in_extent, b))


def resolve_splits(
    layer: Layer,
    granularity,
    min_tile: Mapping[str, int] | None = None,
) -> dict[str, int]:
    """Number of CN splits per splittable dim for `layer` under `granularity`.

    granularity: 'layer' | 'line' | ('tile', n_oy, n_ox) | dict(layer_id->granularity)
    min_tile: HW-dataflow-aware minimum tile extent per dim (max spatial unroll
              across cores); splits are clamped so tiles stay >= min_tile.
    """
    if isinstance(granularity, dict):
        granularity = granularity.get(layer.id, "layer")
    if layer.op in FULL_FANIN_OPS or granularity == "layer":
        return {}
    oy, ox = layer.d("OY"), layer.d("OX")
    if granularity == "line":
        want = {"OY": oy, "OX": 1}
    elif isinstance(granularity, tuple) and granularity[0] == "tile":
        want = {"OY": int(granularity[1]), "OX": int(granularity[2]) if len(granularity) > 2 else 1}
    else:
        raise ValueError(f"unknown granularity {granularity!r}")
    splits = {}
    for dim, extent in (("OY", oy), ("OX", ox)):
        n = min(want.get(dim, 1), extent)
        if min_tile and dim in min_tile and min_tile[dim] > 1:
            n = min(n, max(1, extent // min_tile[dim]))
        if n > 1:
            splits[dim] = n
    return splits


def identify_cns(
    workload: Workload,
    granularity="line",
    min_tile: Mapping[str, int] | None = None,
) -> list[CN]:
    """Split every layer of `workload` into CNs (Stream Step 1).

    All per-dimension work (receptive ranges, exclusive/fresh extents,
    output fractions) is precomputed once per layer and position; the
    per-CN loop only combines the per-position lookups, so splitting a
    layer into k CNs is O(k), not O(k x dims x receptive math).
    """
    cns: list[CN] = []
    for lid in workload.topo_order():
        layer = workload.layers[lid]
        splits = resolve_splits(layer, granularity, min_tile)
        if layer.mapped:
            cns.extend(_mapped_cns(workload, layer, splits, len(cns)))
            continue
        for p in layer.inputs:
            prod = workload.layers[p]
            if prod.rows is not None or prod.d("B") != layer.d("B"):
                raise ValueError(
                    f"layer {layer.name} reads {prod.name}, whose rows are "
                    f"routed or whose batch axis differs: give it `reads`")
        dims = [d for d in SPLITTABLE if d in splits]
        _, _, iy_ext, ix_ext = layer.in_shape
        total_out = layer.out_elems
        layer_macs = layer.macs
        b_ext, k_ext, c_ext = layer.d("B"), layer.d("K"), layer.d("C")
        stride, pad = layer.stride, layer.padding
        wb, bits, op = layer.weight_bytes, layer.bits, layer.op

        # ---- per-dim precomputation (positions along each splittable dim) --
        # Every SPLITTABLE dim has a list of output ranges (length 1 when not
        # split), their input receptive ranges, the exclusive / fresh input
        # extents per position (paper Fig. 5), and the output fraction.
        out_rng: dict[str, list[tuple[int, int]]] = {}
        rcv: dict[str, list[tuple[int, int]]] = {}
        ext_excl: dict[str, list[int]] = {}
        ext_new: dict[str, list[int]] = {}
        frac_of: dict[str, list[float]] = {}
        for d in SPLITTABLE:
            tot = layer.d(d)
            rs = _split_ranges(tot, splits[d]) if d in splits else [(0, tot)]
            fsize = layer.d("FY" if d == "OY" else "FX")
            in_ext = iy_ext if d == "OY" else ix_ext
            rc = [_receptive(r, stride, fsize, pad, in_ext) for r in rs]
            xs, ns = [], []
            for pos, (a, b) in enumerate(rc):
                e_excl = e_new = max(0, b - a)
                if pos + 1 < len(rc):
                    e_excl = max(0, min(b, rc[pos + 1][0]) - a)
                if pos > 0:
                    e_new = max(0, b - max(a, rc[pos - 1][1]))
                xs.append(e_excl)
                ns.append(e_new)
            out_rng[d], rcv[d] = rs, rc
            ext_excl[d], ext_new[d] = xs, ns
            frac_of[d] = [(b - a) / tot for a, b in rs]
        grid = [len(out_rng[d]) for d in dims]
        n_cn = math.prod(grid) if grid else 1

        # per-producer K ranges (CN-independent): consumer input space; concat
        # rects carry the channel offset of each producer within the
        # concatenated K axis, so per-producer claims partition [0, K)
        # instead of all aliasing [0, pk)
        producers = layer.inputs if layer.inputs else (-1,)
        prod_k: list[tuple[int, int, int]] = []  # (producer, ka, kb)
        ch_off = 0
        for p in producers:
            if op == "concat":
                pk = workload.layers[p].d("K") if p >= 0 else c_ext
                prod_k.append((p, ch_off, ch_off + pk))
                ch_off += pk
            elif op in ("dwconv", "pool", "add"):
                prod_k.append((p, 0, k_ext))
            else:  # conv / fc need all input channels
                prod_k.append((p, 0, c_ext))
        sum_k = sum(kb - ka for _, ka, kb in prod_k)
        b_clamped = max(0, b_ext)

        for rank in range(n_cn):
            # decode row-major multi-index
            idx, rem = [], rank
            for g in reversed(grid):
                idx.append(rem % g)
                rem //= g
            idx = tuple(reversed(idx))
            pos = dict(zip(dims, idx))
            pos_oy, pos_ox = pos.get("OY", 0), pos.get("OX", 0)

            frac = 1.0
            for d, i in zip(dims, idx):
                frac *= frac_of[d][i]
            oy_a, oy_b = out_rng["OY"][pos_oy]
            ox_a, ox_b = out_rng["OX"][pos_ox]
            out_rect = Rect((("B", 0, b_ext), ("K", 0, k_ext),
                             ("OY", oy_a, oy_b), ("OX", ox_a, ox_b)))

            # input rect per producer operand (consumer input space)
            iy = rcv["OY"][pos_oy]
            ix = rcv["OX"][pos_ox]
            in_rects: dict[int, Rect] = {
                p: Rect((("B", 0, b_ext), ("K", ka, kb),
                         ("OY", iy[0], iy[1]), ("OX", ix[0], ix[1])))
                for p, ka, kb in prod_k}

            # ---- attribute extraction (paper Fig. 5) -----------------------
            # exclusive input volume: Π_d extent-before-next-CN's-input-start
            # fresh input volume:     Π_d extent-after-prev-CN's-input-stop
            # (per-dim extents looked up from the per-position tables; the
            # per-producer K extents factor out of the dim product)
            base = b_clamped * sum_k
            discardable = base * ext_excl["OY"][pos_oy] * ext_excl["OX"][pos_ox]
            fresh = base * ext_new["OY"][pos_oy] * ext_new["OX"][pos_ox]

            macs = max(1, round(layer_macs * frac))
            new_out = max(1, round(total_out * frac)) if total_out else 0

            cns.append(CN(
                id=len(cns), layer=lid, idx=idx, intra_rank=rank,
                out_rect=out_rect, in_rects=in_rects, macs=macs,
                discardable_inputs=discardable, new_inputs=fresh, new_outputs=new_out,
                weight_bytes=wb, in_bits=bits, out_bits=bits,
            ))
    return cns


def default_channels(layer: Layer, producer: Layer) -> tuple[int, int]:
    """Channel slice of `producer` that `layer` reads when `reads` does not
    say: C channels for conv/fc, all of a matmul operand, K otherwise."""
    if layer.op in ("conv", "fc"):
        return (0, layer.d("C"))
    if layer.op in ACT_OPERAND_OPS:
        return (0, producer.d("K"))
    return (0, layer.d("K"))


def input_ports(workload: Workload, layer: Layer) -> list[tuple]:
    """Per input of a mapped layer: (producer id, channel lo, channel hi,
    role, whether the channels lie on the causal key axis). The slice is
    clipped to the producer's channels."""
    n = len(layer.inputs)
    reads = layer.reads or (None,) * n
    roles = layer.roles or ("a",) * n
    key_in_channels = {"K": layer.op not in ACT_OPERAND_OPS,
                       "C": layer.op in ACT_OPERAND_OPS}.get(layer.causal,
                                                             False)
    ports = []
    for p, rd, role in zip(layer.inputs, reads, roles):
        prod = workload.layers[p]
        lo, hi = rd if rd is not None else default_channels(layer, prod)
        ports.append((p, max(lo, 0), min(hi, prod.d("K")), role,
                      role == "a" and key_in_channels))
    return ports


def input_rows(workload: Workload, layer: Layer, p: int, role: str,
               a: int, b: int) -> tuple[int, int, int]:
    """Rows of producer `p` that the CN of `layer` producing rows [a, b)
    reads, as (first, stop, count) in the producer's OY index space.

    Operand B reads the causal prefix [0, b), or all rows; other inputs
    read their band. A routed layer reading token-space rows reads its
    own tokens (count rows between first and stop, which are not all
    read); a token-space layer reading a routed producer reads the
    producer rows routed from its band, a contiguous index range."""
    prod = workload.layers[p]
    if role == "b":
        stop = b if layer.causal is not None else prod.d("OY")
        if layer.rows is not None or prod.rows is not None:
            raise ValueError(f"{layer.name}: operand B rows are not routed")
        return 0, stop, stop
    if layer.rows == prod.rows:
        return a, b, b - a
    if prod.rows is None:
        tokens = layer.rows[a:b]
        return tokens[0], tokens[-1] + 1, b - a
    if layer.rows is None:
        lo, hi = np.searchsorted(prod.rows, (a, b)).tolist()
        return lo, hi, hi - lo
    raise ValueError(f"{layer.name} and {prod.name} route different rows")


def _mapped_cns(workload: Workload, layer: Layer, splits: dict[str, int],
                first_id: int) -> list[CN]:
    """CNs of a layer that says what it reads: one per band of its token
    rows OY, each reading its inputs' ports (see `input_ports`,
    `input_rows`). Exclusive and fresh input volumes compare each band's
    reads with the next and the previous band's."""
    if ("OX" in splits or layer.stride != 1 or layer.padding
            or layer.d("FY") != 1 or layer.d("FX") != 1):
        raise ValueError(f"{layer.name}: a mapped layer is pointwise along "
                         f"its token rows and split along them only")
    if not layer.inputs or len(set(layer.inputs)) != len(layer.inputs):
        raise ValueError(f"{layer.name}: a mapped layer reads other layers, "
                         f"each once")
    oy = layer.d("OY")
    key = layer.causal
    if key is not None and layer.d(key) != oy:
        raise ValueError(f"{layer.name}: causal key axis {key} must have "
                         f"the extent of OY")
    bands = _split_ranges(oy, splits.get("OY", 1))
    ports = input_ports(workload, layer)
    b_ext, k_ext, c_ext, ox = (layer.d("B"), layer.d("K"), layer.d("C"),
                               layer.d("OX"))
    elementwise = layer.op in ("add", "concat")

    reads = []      # per band: [(rect, elements read, routed?)] per port
    for a, b in bands:
        band = []
        for p, lo, hi, role, on_key in ports:
            prod = workload.layers[p]
            r0, r1, count = input_rows(workload, layer, p, role, a, b)
            c1 = min(hi, lo + b) if on_key else hi
            rect = Rect((("B", 0, prod.d("B")), ("K", lo, c1),
                         ("OY", r0, r1), ("OX", 0, prod.d("OX"))))
            vol = prod.d("B") * max(c1 - lo, 0) * count * prod.d("OX")
            band.append((rect, vol, count != r1 - r0))
        reads.append(band)

    def shared(t: int, u: int) -> int:
        """Input elements that bands t and u both read."""
        if not 0 <= u < len(bands):
            return 0
        return sum(0 if sparse else rt.intersection_volume(ru)
                   for (rt, _, sparse), (ru, _, _) in zip(reads[t], reads[u]))

    cns = []
    for t, (a, b) in enumerate(bands):
        k_cn = b if key == "K" else k_ext
        c_cn = b if key == "C" else c_ext
        out_rect = Rect((("B", 0, b_ext), ("K", 0, k_cn), ("OY", a, b),
                         ("OX", 0, ox)))
        if elementwise:
            macs = b_ext * k_cn * (b - a) * ox
        else:
            macs = b_ext * k_cn * c_cn * (b - a) * ox
        vol = sum(v for _, v, _ in reads[t])
        cns.append(CN(
            id=first_id + t, layer=layer.id,
            idx=(t,) if len(bands) > 1 else (), intra_rank=t,
            out_rect=out_rect,
            in_rects={p: rect for (p, *_), (rect, _, _)
                      in zip(ports, reads[t])},
            macs=macs, discardable_inputs=vol - shared(t, t + 1),
            new_inputs=vol - shared(t, t - 1),
            new_outputs=out_rect.volume(), weight_bytes=layer.weight_bytes,
            in_bits=layer.bits, out_bits=layer.bits,
            reduce=(("C", c_cn),) if key == "C" else ()))
    return cns


def cns_by_layer(cns: Sequence[CN]) -> dict[int, list[CN]]:
    out: dict[int, list[CN]] = {}
    for cn in cns:
        out.setdefault(cn.layer, []).append(cn)
    for lst in out.values():
        lst.sort(key=lambda c: c.intra_rank)
    return out
