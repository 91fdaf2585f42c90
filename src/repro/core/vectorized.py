"""Batched approximate schedule fitness: the JAX/Pallas population path.

`ScheduleEngine.evaluate_population` walks a Python event loop one CN at a
time per genome — exact, but the throughput ceiling of every GA sweep.
`BatchedFitness` lowers the `record=False` fitness computation to JAX and
evaluates a whole `(P, G)` population at once:

* the CSR `CNGraph` is *wavefront-levelized* (CNs grouped by longest-path
  depth, members in CN-id order — a topological order by construction);
* one `lax.scan` step per wavefront computes every member's ready time
  from predecessor finishes, channel transfers, DRAM weight/input fetches
  and fused-stack barriers, all batched over the population axis;
* FCFS contention (cores, bus/link channels, the DRAM port) is
  approximated as per-resource *prefix serialization* within the wavefront,
  the one contention model on every platform: the queue recurrence
  ``f_k = max(f_{k-1}, r_k) + d_k`` unrolls into cumsum/cummax prefix ops
  (`repro.kernels.ref.serialize_prefix_ref`), and the per-wavefront core
  and channel updates run as a Pallas kernel
  (`repro.kernels.wavefront.serialize_prefix`) when `use_pallas` is on —
  interpreted only on the CPU (`repro.backend`). `use_pallas` picks the
  backend, never the scores' model;
* activation-memory overflow is always modelled: spilled bytes are written
  to and read back from DRAM through the port's busy time and energy.

The result is a *fitness approximation*: global heap order collapses to
wavefront order, fresh-byte dedup and spill feedback are dropped, weights
are fetched once per layer, and external inputs lose their just-in-time
staging. Scores therefore only *rank* genomes — `GeneticAllocator` uses
them as a prefilter that prunes each offspring batch to plausible NSGA-II
survivors, which the exact engine re-scores (`rescore`), keeping every
stored metric bit-identical. `latency_lower_bound` is the provable
counterpart (no-contention critical path, per-core work, mandatory DRAM
traffic): it never exceeds the exact latency beyond float rounding.

    >>> import numpy as np
    >>> round(rank_correlation(np.array([1.0, 2.0, 3.0, 4.0]),
    ...                        np.array([10.0, 20.0, 30.0, 40.0])), 6)
    1.0
"""
from __future__ import annotations

import contextlib
import math

import numpy as np

BIG = 1e30      # cycles stand-in for infeasible (CN, core) pairs
NEG = -1e30     # release-time stand-in for "not queued on this resource"

_OBJECTIVES = ("edp", "latency", "energy")
_NO_SPAN = contextlib.nullcontext()    # `scores` without a tracer


def rank_correlation(a, b) -> float:
    """Spearman rank correlation of two score vectors (ordinal ranks).

    The prefilter contract is *ranking*, so this — not absolute error — is
    the figure of merit comparing approximate and exact fitness.

        >>> rank_correlation([3.0, 1.0, 2.0], [30.0, 10.0, 20.0])
        1.0
        >>> rank_correlation([1.0, 2.0], [2.0, 1.0])
        -1.0
    """
    a = np.asarray(a, dtype=float).ravel()
    b = np.asarray(b, dtype=float).ravel()
    if a.size != b.size or a.size < 2:
        raise ValueError("need two equal-length vectors of >= 2 scores")
    ra = np.empty(a.size)
    rb = np.empty(b.size)
    ra[np.argsort(a, kind="stable")] = np.arange(a.size)
    rb[np.argsort(b, kind="stable")] = np.arange(b.size)
    ra -= ra.mean()
    rb -= rb.mean()
    denom = math.sqrt(float(np.dot(ra, ra)) * float(np.dot(rb, rb)))
    return float(np.dot(ra, rb) / denom) if denom else 0.0


def _pow2_at_least(k: int) -> int:
    return 1 << max(k - 1, 1).bit_length() if k > 1 else 1


def core_select(idx, table):
    """`table[idx]` for a genome-dependent index `idx` into a core axis.

    `table[c]` is core `c`'s value (the leading axis of an array, or a
    list of per-core arrays) and broadcasts against `idx`. The index is
    compared against each core and the match selected, so the result is
    the table's own bits: no per-element gather, which the TPU serves
    slowly, and no one-hot product, which `BIG`, `NEG` and `0 * BIG` would
    change. An index past the last core takes the last entry. The chain
    is as long as the axis, so it serves core axes (at most 17 in the
    catalog), never an axis of layers or segments.

        >>> import jax.numpy as jnp
        >>> core_select(jnp.array([2, 0, 1]), jnp.array([5.0, 6.0, 7.0]))
        Array([7., 5., 6.], dtype=float32)
    """
    import jax.numpy as jnp
    last = len(table) - 1
    out = jnp.broadcast_to(table[last], jnp.broadcast_shapes(
        jnp.shape(idx), jnp.shape(table[last])))
    for c in range(last - 1, -1, -1):
        out = jnp.where(idx == c, table[c], out)
    return out


class BatchedFitness:
    """Vectorized approximate (latency, energy) for genome populations.

    Binds one `ScheduleEngine` (graph + cost tables + accelerator
    constants) and compiles a jitted wavefront scan over its CN graph.
    `scores` approximates, `rescore` delegates to the exact engine, and
    `prefilter` packages the scalarized approximate score for
    `GeneticAllocator(prefilter=...)`.

    `use_pallas=None` enables the Pallas serialization kernel unless the
    work runs on the CPU (`repro.backend.platform`); `True` forces it
    (interpreted on CPU), `False` keeps the pure-jnp reference path. Both
    run the same contention and spill model.
    """

    def __init__(self, engine, priority: str = "latency",
                 segment: bool = True, strict_layers: bool = False,
                 use_pallas: bool | None = None, max_batch: int = 256):
        if priority not in ("latency", "memory"):
            raise ValueError(f"unknown priority {priority!r}")
        self.engine = engine
        self.priority = priority
        self.segment = segment
        self.strict_layers = strict_layers
        self.max_batch = int(max_batch)
        import jax

        from repro.backend import platform
        if use_pallas is None:
            use_pallas = platform() != "cpu"
        self.use_pallas = bool(use_pallas)
        self._build_static()
        self._score_fn = jax.jit(self._score)

    # ---- static precompute (numpy, once per engine binding) ---------------
    def _build_static(self) -> None:
        import jax.numpy as jnp
        eng = self.engine
        graph = eng.graph
        acc = eng.accelerator
        n = graph.n
        n_cores = acc.n_cores
        self.n, self.n_cores = n, n_cores
        self.n_layers = eng.n_layers

        indptr = graph.pred_indptr
        idx = graph.pred_indices
        byt = graph.pred_bytes
        cons = np.repeat(np.arange(n), np.diff(indptr))
        if idx.size and not bool(np.all(idx < cons)):
            raise ValueError("CN ids are not a topological order")

        # longest-path levels -> wavefronts (members kept in CN-id order)
        level = np.zeros(n, dtype=np.int64)
        ptr = indptr.tolist()
        preds = [idx[ptr[v]:ptr[v + 1]] for v in range(n)]
        for v in range(n):
            if preds[v].size:
                level[v] = int(level[preds[v]].max()) + 1
        n_levels = int(level.max()) + 1 if n else 1
        counts = np.bincount(level, minlength=n_levels)
        width = int(counts.max()) if n else 1
        wf = np.full((n_levels, width), n, dtype=np.int32)
        slot = np.zeros(n_levels, dtype=np.int64)
        for v in range(n):  # id order per level == FCFS service order
            lv = level[v]
            wf[lv, slot[lv]] = v
            slot[lv] += 1
        self.n_wavefronts, self.width = n_levels, width

        dmax = int(np.diff(indptr).max()) if n and idx.size else 0
        pred_ids = np.full((n + 1, dmax), n, dtype=np.int32)
        pred_b = np.zeros((n + 1, dmax), dtype=np.float32)
        for v in range(n):
            k = ptr[v + 1] - ptr[v]
            if k:
                pred_ids[v, :k] = idx[ptr[v]:ptr[v + 1]]
                pred_b[v, :k] = byt[ptr[v]:ptr[v + 1]]
        self.dmax = dmax
        # per-wavefront static views (gathered once here instead of per
        # scan step): predecessor slots and edge-existence masks
        wf_pred = pred_ids[wf] if dmax else np.zeros(
            (n_levels, width, 1), dtype=np.int32)
        wf_edge = (pred_b[wf] > 0) if dmax else np.zeros(
            (n_levels, width, 1), dtype=bool)

        # successor lists (producer-side view of the same edges) + the map
        # from pred slot (v, d) to the producer's succ slot — fresh-byte
        # dedup is defined over each producer's consumers in id order
        sptr = graph.succ_indptr.tolist()
        sidx = graph.succ_indices
        sbyt = graph.succ_bytes
        smax = int(np.diff(graph.succ_indptr).max()) if n and sidx.size else 0
        succ_ids = np.full((n + 1, max(smax, 1)), n, dtype=np.int32)
        succ_b = np.zeros((n + 1, max(smax, 1)), dtype=np.float32)
        slot_of = {}
        for u in range(n):
            k = sptr[u + 1] - sptr[u]
            for s in range(k):
                v = int(sidx[sptr[u] + s])
                succ_ids[u, s] = v
                succ_b[u, s] = sbyt[sptr[u] + s]
                slot_of[(u, v)] = s
        edge_slot = np.zeros((n + 1, dmax), dtype=np.int32)
        for v in range(n):
            for d in range(ptr[v + 1] - ptr[v]):
                edge_slot[v, d] = slot_of[(int(idx[ptr[v] + d]), v)]
        self.smax = max(smax, 1)
        # footprint pieces (LLM graphs, see `CNGraph.footprint_pieces`):
        # flat successor slot u * smax + s of each piece's readers, -1 pad
        pieces = None
        if graph.footprints is not None:
            p_u, p_slots, p_bytes = graph.footprint_pieces
            flat = np.where(p_slots >= 0, p_u[:, None] * self.smax + p_slots,
                            -1).astype(np.int32)
            pieces = (flat, p_bytes.astype(np.float32))

        tab = eng.tables
        feas = tab.feasible.astype(bool)
        cyc = np.where(feas, tab.cycles, BIG).astype(np.float32)
        ecs = np.where(feas, tab.e_compute + tab.e_sram, BIG).astype(np.float32)
        sig = tab.sig_of_cn
        cyc_nc = np.zeros((n + 1, n_cores), dtype=np.float32)
        ecs_nc = np.zeros((n + 1, n_cores), dtype=np.float32)
        cyc_nc[:n] = cyc[sig]
        ecs_nc[:n] = ecs[sig]

        layer_pad = np.zeros(n + 1, dtype=np.int32)
        layer_pad[:n] = graph.layer
        head = np.zeros(n + 1, dtype=bool)
        if n:
            head[:n] = np.arange(n) == np.searchsorted(
                graph.layer, graph.layer)
        head_wb = np.where(head[:n], graph.weight_bytes, 0).astype(np.float64)
        ext_b = np.where(np.asarray(eng._external_of, dtype=bool),
                         np.asarray(eng._new_in_bytes, dtype=np.float64), 0.0)

        dram_bw = float(acc.dram_bw_bits_per_cc)
        self._dram_cc_per_byte = 8.0 / dram_bw
        dram_wt = np.zeros(n + 1, dtype=np.float32)
        dram_ext = np.zeros(n + 1, dtype=np.float32)
        dram_wt[:n] = head_wb * self._dram_cc_per_byte
        dram_ext[:n] = ext_b * self._dram_cc_per_byte
        # DRAM-port FCFS offsets are genome-independent (service order is
        # wavefront slot order, releases all 0): per wavefront, the end
        # offset of each member's external-input and weight fetch relative
        # to the port's free time on entry — NEG marks "no fetch"
        d_ext = dram_ext[wf]                       # (L, W)
        d_wt = dram_wt[wf]
        tot = d_ext + d_wt
        pre = np.cumsum(tot, axis=1) - tot
        ext_off = np.where(d_ext > 0, pre + d_ext, NEG).astype(np.float32)
        wt_off = np.where(d_wt > 0, pre + tot, NEG).astype(np.float32)
        dram_off = np.maximum(ext_off, wt_off)     # one fused ready bound
        dram_tot = tot.sum(axis=1).astype(np.float32)  # (L,)

        # activation-memory accounting (the spill model): per-wavefront
        # allocated / discarded bytes and per-edge bytes for readbacks
        out_pad = np.concatenate(
            [np.asarray(eng._out_bytes, dtype=np.float64), [0.0]])
        ext_pad = np.concatenate([ext_b, [0.0]])
        disc_pad = np.concatenate(
            [np.asarray(eng._disc_bytes, dtype=np.float64), [0.0]])
        alloc_b = (out_pad + ext_pad)[wf].astype(np.float32)    # (L, W)
        disc_b = disc_pad[wf].astype(np.float32)
        wf_pb = (pred_b[wf] if dmax else
                 np.zeros_like(wf_pred, dtype=np.float32))       # (L, W, D)
        self._act_cap = np.asarray(eng._act_cap0, dtype=np.float32)
        # mandatory off-chip traffic: once-per-layer weights + external
        # inputs — both a constant energy term and the DRAM-port floor of
        # `latency_lower_bound`
        self._dram_bytes_const = float(head_wb.sum() + ext_b.sum())
        self._dram_e_per_byte = 8.0 * float(acc.dram_energy_pj_per_bit)
        self._dram_e_const = self._dram_bytes_const * self._dram_e_per_byte
        self._dram_cc_const = self._dram_bytes_const * self._dram_cc_per_byte

        # channel routes flattened to dense core-pair tables; the flat bus
        # is channel 0 of a 1-channel fabric, shared-L1 has no transfers
        self.shared_l1 = bool(eng._shared_l1)
        if self.shared_l1:
            n_chan = 0
            route_inv = np.zeros((n_cores, n_cores, 1), dtype=np.float32)
            route_e = np.zeros((n_cores, n_cores), dtype=np.float32)
        elif eng._routes is not None:
            n_chan = eng._n_chan
            route_inv = np.zeros((n_cores, n_cores, n_chan), dtype=np.float32)
            route_e = np.zeros((n_cores, n_cores), dtype=np.float32)
            for u in range(n_cores):
                for v in range(n_cores):
                    if u == v:
                        continue
                    for ch in eng._routes[u][v]:
                        route_inv[u, v, ch] += 1.0 / eng._chan_bw[ch]
                        route_e[u, v] += eng._chan_e[ch]
        else:
            n_chan = 1
            off = 1.0 - np.eye(n_cores, dtype=np.float32)
            route_inv = (off / float(acc.bus_bw_bits_per_cc))[:, :, None]
            route_e = off * float(acc.bus_energy_pj_per_bit)
        self.n_chan = n_chan

        self._j = {
            "wf": jnp.asarray(wf),
            "member": jnp.asarray(wf < n),
            "wf_pred": jnp.asarray(wf_pred),
            "wf_edge": jnp.asarray(wf_edge),
            "pred_ids": jnp.asarray(pred_ids),
            "pred_b": jnp.asarray(pred_b),
            "succ_ids": jnp.asarray(succ_ids),
            "succ_b": jnp.asarray(succ_b),
            "edge_slot": jnp.asarray(edge_slot),
            "out_bytes": jnp.asarray(
                np.concatenate([graph.out_bytes, [0]]).astype(np.float32)),
            "cyc_cn": jnp.asarray(cyc_nc.T[:, :, None]),   # (C, n+1, 1)
            "ecs_cn": jnp.asarray(ecs_nc.T[:, :, None]),
            "layer_pad": jnp.asarray(layer_pad),
            "dram_off": jnp.asarray(dram_off),
            "dram_tot": jnp.asarray(dram_tot),
            "alloc_b": jnp.asarray(alloc_b),
            "disc_b": jnp.asarray(disc_b),
            "wf_pb": jnp.asarray(wf_pb),
            "act_cap": jnp.asarray(self._act_cap),
            "route_inv": jnp.asarray(route_inv),
            "route_e": jnp.asarray(route_e),
            "layer_wb": jnp.asarray(
                np.asarray(eng._layer_wb, dtype=np.float32)),
            "w_cap": jnp.asarray(np.asarray(eng._w_cap, dtype=np.float32)),
        }
        if pieces is not None:
            self._j["piece_slot"] = jnp.asarray(pieces[0])
            self._j["piece_b"] = jnp.asarray(pieces[1])
        # (n+1, L) one-hot of each CN's wavefront level (pad row all-zero):
        # projects per-CN byte columns onto per-level sums with one matmul
        lvl_oh = np.zeros((n + 1, n_levels), dtype=np.float32)
        lvl_oh[np.arange(n), level] = 1.0
        self._j["lvl_oh"] = jnp.asarray(lvl_oh)

        # numpy copies for the float64 lower bound
        self._np_pred_ids = pred_ids
        self._np_cyc64 = np.where(feas, tab.cycles, BIG)[sig]  # (n, C)
        self._np_layer = np.asarray(graph.layer, dtype=np.int64)

        if self.use_pallas:
            from repro.kernels.wavefront import serialize_prefix

            def _ser(free0, release, dur):
                return serialize_prefix(free0, release, dur)
        else:
            from repro.kernels.ref import serialize_prefix_ref as _ser
        self._serialize = _ser

        def _ser_t(free0, release, dur):
            # population-last wrapper: (R, P) free + (R, W, P) items — the
            # kernel wants FCFS item order on the minor axis, so pivot to
            # (P, R, W) rows around the call (small per-step tiles only)
            fin, free = _ser(free0.T, release.transpose(2, 0, 1),
                             dur.transpose(2, 0, 1))
            return fin.transpose(1, 2, 0), free.T
        self._serialize_t = _ser_t

    # ---- jitted scoring ---------------------------------------------------
    def _segments(self, cores_gl):
        """(P, G) fused-stack segment ids replicating `_segments_from_arrays`
        (greedy cut when a core's accumulated weight footprint overflows)."""
        import jax
        import jax.numpy as jnp
        j = self._j
        p = cores_gl.shape[0]
        n_cores = self.n_cores
        on_core = jnp.arange(n_cores)[:, None]

        def step(carry, x):
            acc_w, seg = carry                         # (C, P), (P,)
            core, wb = x
            cap = core_select(core, j["w_cap"])
            hold = jnp.minimum(wb, cap)
            held = core_select(core, acc_w)
            active = (wb > 0) & (cap > 0)
            cut = active & (held + hold > cap) & (held > 0)
            seg = seg + cut.astype(seg.dtype)
            acc_w = jnp.where(cut[None], 0.0, acc_w)
            # adding 0.0 leaves every other core's footprint as it was
            add = jnp.where(active, hold, 0.0)
            acc_w = acc_w + jnp.where(core == on_core, add, 0.0)
            return (acc_w, seg), seg

        init = (jnp.zeros((n_cores, p), jnp.float32),
                jnp.zeros(p, jnp.int32))
        (_, _), segs = jax.lax.scan(
            step, init, (cores_gl.T, j["layer_wb"]))
        return segs.T

    def _score(self, genomes):
        """genomes (P, G) int32 -> (latency (P,), energy (P,)) float32."""
        import jax
        import jax.numpy as jnp

        j = self._j
        n, n_cores, n_chan = self.n, self.n_cores, self.n_chan
        n_seg = self.n_layers
        p = genomes.shape[0]

        if self.strict_layers:
            seg_gl = jnp.broadcast_to(
                jnp.arange(self.n_layers, dtype=jnp.int32)[None],
                genomes.shape)
        elif self.segment:
            seg_gl = self._segments(genomes)
        else:
            seg_gl = jnp.zeros(genomes.shape, jnp.int32)

        # population-last layout throughout: per-CN tables are (n+1, P),
        # per-level slices (W, P) — gathers over the leading CN/level axis
        # land directly in scan layout (no large transposes) and every
        # reduction runs over a leading axis with P as the contiguous
        # SIMD-friendly minor dimension
        core_ng = genomes.T[j["layer_pad"]]           # (n+1, P)
        seg_ng = seg_gl.T[j["layer_pad"]]
        cyc_ng = core_select(core_ng, j["cyc_cn"])    # (n+1, P)
        ecs_ng = core_select(core_ng, j["ecs_cn"])

        # fresh-byte dedup, the engine's shipping rule (`repro.core.
        # scheduler`) hoisted out of the time loop (it depends only on the
        # allocation), with consumers taken in successor-slot order
        fresh8_pred = None
        if not self.shared_l1 and self.dmax and "piece_b" in j:
            # footprints: a piece crosses to a core with its first reader
            # there that sits on another core than the producer (`held[c]`:
            # whether core c has it so far; core n_cores pads the readers,
            # which never cross)
            scr = core_ng[j["succ_ids"]]                  # (n+1, S, P)
            crossing = (j["succ_b"][:, :, None] > 0) & (
                scr != core_ng[:, None])
            flat_c = jnp.concatenate([
                crossing.reshape(-1, p), jnp.zeros((1, p), bool)])
            flat_core = jnp.concatenate([
                scr.reshape(-1, p), jnp.full((1, p), n_cores, scr.dtype)])
            held = [jnp.zeros((j["piece_b"].shape[0], p), bool)] * n_cores
            fresh = jnp.zeros(flat_c.shape, jnp.float32)
            for k in range(j["piece_slot"].shape[1]):
                slot = j["piece_slot"][:, k]              # (Np,), -1 = none
                c_k = flat_core[slot]                     # (Np, P)
                x_k = flat_c[slot]
                pays = x_k & ~core_select(c_k, held)
                held = [h | (x_k & (c_k == c)) for c, h in enumerate(held)]
                fresh = fresh.at[slot].add(
                    jnp.where(pays, j["piece_b"][:, None], 0.0))
            fresh8_pred = 8.0 * fresh[:-1].reshape(
                n + 1, self.smax, p)[j["pred_ids"], j["edge_slot"]]
        elif not self.shared_l1 and self.dmax:
            # a producer ships to a core once — the first crossing consumer
            # on that core pays min(edge bytes, remaining budget), the
            # budget starting at the producer's out_bytes
            ucore = core_ng[:, None]                      # (n+1, 1, P)
            scr = core_ng[j["succ_ids"]]                  # (n+1, S, P)
            crossing = (j["succ_b"][:, :, None] > 0) & (scr != ucore)
            tri = jnp.tril(jnp.ones((self.smax, self.smax), bool), k=-1)
            dup = ((scr[:, :, None] == scr[:, None, :])
                   & crossing[:, None] & tri[None, :, :, None])
            first = crossing & ~jnp.any(dup, axis=2)
            rem = jnp.broadcast_to(j["out_bytes"][:, None],
                                   core_ng.shape).astype(jnp.float32)
            fresh_cols = []
            for s in range(self.smax):
                eb = jnp.where(first[:, s], j["succ_b"][:, s, None], 0.0)
                f = jnp.minimum(eb, rem)
                rem = rem - f
                fresh_cols.append(f)
            fresh_succ = jnp.stack(fresh_cols, axis=1)    # (n+1, S, P)
            fresh8_pred = 8.0 * fresh_succ[
                j["pred_ids"], j["edge_slot"]]            # (n+1, D, P)

        # hoist every genome-dependent per-wavefront gather AND every
        # carry-independent per-level reduction out of the scan: the scan
        # body then touches only small per-step slices (scan xs) plus the
        # carried finish/resource state
        wf = j["wf"]                                   # (L, W)
        member = j["member"]                           # (L, W) bool
        cyc_x = cyc_ng[wf]                             # (L, W, P)
        seg_x = seg_ng[wf]
        cw_x = core_ng[wf]
        xs = {"wf": wf, "member": member, "cyc": cyc_x, "seg": seg_x,
              "cw": cw_x, "dram": j["dram_off"], "tot": j["dram_tot"]}
        comm = self.dmax and not self.shared_l1
        on = ((cw_x[:, None] == jnp.arange(n_cores)[None, :, None, None])
              & member[:, None, :, None])              # (L, C, W, P)
        xs["on"] = on
        if self.dmax:
            xs["pu"] = j["wf_pred"]                    # (L, W, D)
        if comm:
            # bundle each consumer's crossing transfers into one FCFS item
            # per channel: occupancy = sum of its fresh-byte hop times on
            # that channel, release = the latest producer finish — computed
            # on the compact (n+1, D, P) pred view, then gathered per level
            pucn = core_ng[j["pred_ids"]]              # (n+1, D, P)
            crossn = (j["pred_b"][:, :, None] > 0) & (pucn != core_ng[:, None])
            f8n = fresh8_pred * crossn                 # (n+1, D, P)

            def route(table):
                """(C, C, ...) core-pair table -> (n+1, D, P, ...) at each
                edge's (producer, consumer) cores: each producer core's
                column at the consumer's core once per CN, then the
                producer's column per edge."""
                tail = (None,) * (table.ndim - 2)
                cols = [core_select(core_ng[(...,) + tail], table[u])[:, None]
                        for u in range(n_cores)]       # (n+1, 1, P, ...)
                return core_select(pucn[(...,) + tail], cols)

            occn = jnp.sum(f8n[..., None] * route(j["route_inv"]),
                           axis=1)                     # (n+1, P, n_chan)
            xs["cross"] = crossn[wf]                   # (L, W, D, P)
            xs["occ"] = jnp.moveaxis(occn, 2, 1)[wf].transpose(0, 2, 1, 3)
        # bytes allocated per CN on its memory-pool core (own outputs,
        # external inputs, and incoming fresh activations) and bytes
        # freed when the wavefront retires (fully-consumed inputs plus
        # the incoming copies themselves) — reduced to per-core (L, C,
        # P) sums here so the scan only tracks occupancy vs capacity
        aw = jnp.broadcast_to(j["alloc_b"][:, :, None], cyc_x.shape)
        fw = jnp.broadcast_to(j["disc_b"][:, :, None], cyc_x.shape)
        if comm:
            # incoming fresh copies land on the consumer's memory core
            fbn = jnp.sum(f8n, axis=1) / 8.0           # (n+1, P)
            aw = aw + fbn[wf]
        aw = jnp.where(member[:, :, None], aw, 0.0)    # (L, W, P)
        if self.shared_l1:
            # activations pool on core 0 under shared L1
            onm = (member[:, None, :, None] &
                   (jnp.arange(n_cores)[None, :, None, None] == 0))
            xs["mw"] = jnp.zeros_like(cw_x)
        else:
            onm = on
            xs["mw"] = cw_x
        xs["aw"] = aw
        xs["ac"] = jnp.sum(jnp.where(onm, aw[:, None], 0.0), axis=2)
        fc = jnp.sum(jnp.where(onm, fw[:, None], 0.0), axis=2)
        if comm:
            # ...and are freed from the *producer's* core when the
            # consumer finishes: per-core mask-sums over the pred view
            # plus one static matmul onto the consumer's level (at
            # full f32 precision: the TPU's default rounds operands to
            # bf16, which would blur the byte counts)
            fbe = f8n / 8.0                            # (n+1, D, P)
            lvl_t = j["lvl_oh"].T                      # (L, n+1)
            cols = [jnp.matmul(
                lvl_t, jnp.sum(jnp.where(pucn == c, fbe, 0.0), axis=1),
                precision=jax.lax.Precision.HIGHEST)
                for c in range(n_cores)]
            fc = fc + jnp.stack(cols, axis=1)          # (L, C, P)
        xs["fc"] = fc

        def pmax0(a):
            """Inclusive prefix max along axis 0 by shift-doubling."""
            k = 1
            while k < a.shape[0]:
                pad = jnp.full((k,) + a.shape[1:], NEG, a.dtype)
                a = jnp.maximum(a, jnp.concatenate([pad, a[:-k]], axis=0))
                k *= 2
            return a

        def step(state, x):
            (finish, core_free, chan_free, dram_free, seg_front, used,
             spilled, dram_x) = state
            if self.dmax:
                pf = finish[x["pu"]]                   # (W, D, P)
                if comm:
                    base = jnp.max(jnp.where(x["cross"], NEG, pf), axis=1,
                                   initial=0.0)        # same-core producers
                    rel_b = jnp.max(jnp.where(x["cross"], pf, NEG), axis=1,
                                    initial=NEG)       # (W, P) bundle release
                    occ_t = x["occ"]                   # (n_chan, W, P)
                    rel_t = jnp.where(occ_t > 0, rel_b[None], NEG)
                    fin_ch, chan_free = self._serialize_t(
                        chan_free, rel_t, occ_t)
                    arr = jnp.max(jnp.where(occ_t > 0, fin_ch, NEG), axis=0)
                    data_ready = jnp.maximum(base, arr)
                else:
                    data_ready = jnp.max(pf, axis=1, initial=0.0)
            else:
                data_ready = jnp.zeros((self.width, p), jnp.float32)

            # DRAM port: external inputs then layer-head weights, FCFS in
            # wavefront order (release 0 — JIT prefetch staging is
            # dropped); end offsets are static, NEG marks "no fetch"
            ready = jnp.maximum(data_ready,
                                dram_free[None] + x["dram"][:, None])
            dram_free = dram_free + x["tot"]

            # fused-stack barrier: a segment starts no earlier than the max
            # finish of every earlier segment (exclusive prefix-max over
            # the per-segment frontiers, gathered per item)
            ex = jnp.concatenate(
                [jnp.full((1, p), NEG), pmax0(seg_front)[:-1]], axis=0)
            barrier = jnp.take_along_axis(ex, x["seg"], axis=0)
            ready = jnp.maximum(ready, barrier)

            # per-core FCFS queue update — the (n_cores x P) step
            mem = x["member"][:, None]
            on_core = x["on"]                          # (C, W, P)
            rel_c = jnp.where(on_core, ready[None], NEG)
            dur_c = jnp.where(on_core, x["cyc"][None], 0.0)
            fin_c, core_free = self._serialize_t(core_free, rel_c, dur_c)
            fin_w = jnp.sum(jnp.where(on_core, fin_c, 0.0), axis=0)

            # activation-memory occupancy and spills, aggregated per
            # wavefront: overflow beyond a core's activation capacity is
            # written out (`spill_w`) and every consumer edge of a spilled
            # producer reads its share back (`spill_r`), both through the
            # DRAM port — the term that dominates exact-energy variance
            alloc_c = x["ac"]                          # (C, P)
            over = jnp.clip(used + alloc_c - j["act_cap"][:, None],
                            0.0, alloc_c)
            frac = over / jnp.maximum(alloc_c, 1.0)
            frac_w = core_select(x["mw"], frac)
            spilled = spilled.at[x["wf"]].add(
                jnp.where(mem, x["aw"] * frac_w, 0.0))
            dram_x = dram_x + jnp.sum(over, axis=0)
            used = jnp.maximum(
                jnp.minimum(used + alloc_c - over, j["act_cap"][:, None])
                - x["fc"], 0.0)

            finish = finish.at[x["wf"]].set(fin_w)
            seg_front = seg_front.at[x["seg"], jnp.arange(p)[None]].max(
                jnp.where(mem, fin_w, NEG))
            return (finish, core_free, chan_free, dram_free, seg_front,
                    used, spilled, dram_x), None

        state = (jnp.zeros((n + 1, p), jnp.float32),
                 jnp.zeros((n_cores, p), jnp.float32),
                 jnp.zeros((max(n_chan, 1), p), jnp.float32),
                 jnp.zeros(p, jnp.float32),
                 jnp.zeros((n_seg, p), jnp.float32),
                 jnp.zeros((n_cores, p), jnp.float32),
                 jnp.zeros((n + 1, p), jnp.float32),
                 jnp.zeros(p, jnp.float32))
        (finish, core_free, chan_free, dram_free, _, _, spilled, dram_x), _ \
            = jax.lax.scan(step, state, xs)

        if self.dmax:
            # spill readback resolves post-scan: a CN spills exactly once,
            # at its own level, and every consumer sits at a strictly later
            # level — so the per-edge min(spilled[producer], edge_bytes)
            # reads the same value after the scan as it would inside it
            dram_x = dram_x + jnp.sum(
                jnp.minimum(spilled[j["pred_ids"]], j["pred_b"][:, :, None]),
                axis=(0, 1))

        # spill traffic occupies the DRAM port too, but its interleaving
        # with the fetch stream is timing-dependent — account for it as a
        # lump extension of the port busy time (keeps the term monotone in
        # spilled bytes without per-step noise in every ready time)
        latency = jnp.maximum(jnp.max(finish, axis=0),
                              dram_free + dram_x * self._dram_cc_per_byte)
        latency = jnp.maximum(latency, jnp.max(chan_free, axis=0))
        energy = (jnp.sum(ecs_ng[:n], axis=0) + self._dram_e_const
                  + dram_x * self._dram_e_per_byte)
        if comm:
            energy = energy + jnp.sum(f8n * route(j["route_e"]),
                                      axis=(0, 1))
        return latency, energy

    # ---- public API -------------------------------------------------------
    def _as_matrix(self, genomes) -> np.ndarray:
        g = np.ascontiguousarray(np.asarray(genomes, dtype=np.int64))
        if g.ndim == 1:
            g = g[None, :]
        return g

    def scores(self, genomes) -> np.ndarray:
        """Approximate `(K, 2)` `[latency_cc, energy_pj]` for `(K, G)`
        genomes. Values rank; they are not the engine's exact metrics.

        With a tracer on the engine, the call is a `fitness.scores` span and
        each blocking fetch of a chunk's results a `fitness.wait` span in
        it, so host preparation and the wait for the device come apart."""
        tracer = self.engine.tracer
        if tracer is None:
            return self._scores(genomes, None)
        with tracer.span("fitness.scores"):
            return self._scores(genomes, tracer)

    def _scores(self, genomes, tracer) -> np.ndarray:
        import jax.numpy as jnp
        g = self._as_matrix(genomes)
        k = g.shape[0]
        out = np.empty((k, 2), dtype=np.float64)
        chunk = min(self.max_batch, _pow2_at_least(k))
        for o in range(0, k, chunk):
            part = g[o:o + chunk]
            m = part.shape[0]
            if m < chunk:
                part = np.concatenate(
                    [part, np.repeat(part[-1:], chunk - m, axis=0)])
            lat, en = self._score_fn(jnp.asarray(part, dtype=jnp.int32))
            with (_NO_SPAN if tracer is None
                  else tracer.span("fitness.wait")):
                out[o:o + m, 0] = np.asarray(lat, dtype=np.float64)[:m]
                out[o:o + m, 1] = np.asarray(en, dtype=np.float64)[:m]
        return out

    def rescore(self, genomes) -> np.ndarray:
        """Exact `(K, 2)` metrics through the Python engine — the oracle the
        prefilter's survivors are re-scored with (bit-identical to
        `engine.evaluate`)."""
        return self.engine.evaluate_population(
            self._as_matrix(genomes), self.priority, segment=self.segment,
            strict_layers=self.strict_layers)

    def latency_lower_bound(self, genomes) -> np.ndarray:
        """Provable `(K,)` latency floor: max of the zero-contention
        critical path, the busiest core's total work, and the mandatory
        DRAM traffic time. Never above `engine.evaluate`'s latency (up to
        float-summation rounding; compare with ~1e-9 rtol)."""
        g = self._as_matrix(genomes)
        k, n = g.shape[0], self.n
        core_of = g[:, self._np_layer]                       # (K, n)
        cyc = self._np_cyc64[np.arange(n)[None, :], core_of]  # (K, n)
        cp = np.zeros((k, n + 1), dtype=np.float64)
        pred = self._np_pred_ids
        for v in range(n):
            if self.dmax:
                cp[:, v] = cyc[:, v] + np.max(cp[:, pred[v]], axis=1,
                                              initial=0.0)
            else:
                cp[:, v] = cyc[:, v]
        busy = np.zeros((k, self.n_cores), dtype=np.float64)
        np.add.at(busy, (np.arange(k)[:, None], core_of), cyc)
        lb = np.maximum(cp.max(axis=1), busy.max(axis=1))
        return np.maximum(lb, self._dram_cc_const)

    def prefilter(self, objective: str = "edp"):
        """Batch scorer for `GeneticAllocator(prefilter=...)`: a callable
        mapping `(K, G)` genomes to `(K, M)` approximate objectives in the
        ranking space NSGA-II screening uses for `objective` — "edp" keeps
        both latency and energy columns, single-metric objectives rank on
        their column alone."""
        if objective not in _OBJECTIVES:
            raise ValueError(f"unknown objective {objective!r}")

        def score(genomes: np.ndarray) -> np.ndarray:
            s = self.scores(genomes)
            if objective == "latency":
                return s[:, :1]
            if objective == "energy":
                return s[:, 1:]
            return s

        return score


def get_batched_fitness(engine, priority: str = "latency",
                        segment: bool = True, strict_layers: bool = False,
                        use_pallas: bool | None = None,
                        contention: str = "serialize") -> BatchedFitness:
    """`BatchedFitness` for `engine`, cached on the engine instance so one
    GA run (and every explore() hitting the session's engine cache) pays
    the wavefront precompute and jit trace once per configuration."""
    # `contention` is kept for tests/bench/benchtools.py (serialize_on_host)
    # and tests/bench/test_bench_correct_explore_llm.py; it goes with them
    if contention != "serialize":
        raise ValueError(f"unknown contention model {contention!r}")
    cache = getattr(engine, "_batched_fitness", None)
    if cache is None:
        cache = engine._batched_fitness = {}
    key = (priority, segment, strict_layers, use_pallas)
    bf = cache.get(key)
    if bf is None:
        bf = cache[key] = BatchedFitness(
            engine, priority, segment=segment, strict_layers=strict_layers,
            use_pallas=use_pallas)
    return bf
