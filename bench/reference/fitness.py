"""Plain batched fitness: the reference an explore cell's chip scores are
compared with.

The explorer's prefilter scores whole GA populations with an approximate
schedule (`repro.core.vectorized.BatchedFitness`, contention model
`serialize`, whose per-resource step is the Pallas `serialize_prefix`
kernel on the chip). The approximation is its own definition, so its
reference is a copy of its jnp form, taken when this benchmark was written
and kept here so that no later change to the program moves it, with two
differences: every FCFS queue is served item by item
(`f_k = max(f_{k-1}, r_k) + d_k`), and the whole computation runs in a
dtype of the caller's choice on the host CPU: float64 for the reference,
bfloat16 for the control. It builds its tables from the problem instance
and the plain per-CN costs (`bench.reference.stream_schedule.problem`),
not from the program's scheduler or cost model.

It covers the flat-bus and shared-L1 fabrics, fused stacks cut by weight
capacity and the activation-spill model, as the program's defaults do.
"""
from __future__ import annotations

import functools

import numpy as np

BIG = 1e30      # cycles stand-in for infeasible (CN, core) pairs
NEG = -1e30     # release time of an item not queued on a resource


def fcfs(free0, release, dur):
    """FCFS queues, item by item: free0 (R, P); release, dur (R, W, P) ->
    finish (R, W, P), new free (R, P)."""
    import jax.numpy as jnp
    f = free0
    out = []
    for w in range(release.shape[1]):
        f = jnp.maximum(f, release[:, w]) + dur[:, w]
        out.append(f)
    return jnp.stack(out, axis=1), f


class FitnessReference:
    def __init__(self, graph, cost_model, accelerator):
        if accelerator.topology is not None:
            raise NotImplementedError("the reference models no topology")
        acc = accelerator
        n, C = graph.n, acc.n_cores
        wl = cost_model.workload
        self.n, self.C, self.n_layers = n, C, len(wl.layers)
        hot = graph.hot_lists
        indptr, idx, byt = (graph.pred_indptr, graph.pred_indices,
                            graph.pred_bytes)
        ptr = indptr.tolist()
        level = np.zeros(n, dtype=np.int64)
        for v in range(n):
            if ptr[v + 1] > ptr[v]:
                level[v] = int(level[idx[ptr[v]:ptr[v + 1]]].max()) + 1
        L = int(level.max()) + 1
        W = int(np.bincount(level).max())
        wf = np.full((L, W), n, dtype=np.int32)
        fill = np.zeros(L, dtype=np.int64)
        for v in range(n):
            wf[level[v], fill[level[v]]] = v
            fill[level[v]] += 1
        D = int(np.diff(indptr).max())
        pred_ids = np.full((n + 1, D), n, dtype=np.int32)
        pred_b = np.zeros((n + 1, D))
        for v in range(n):
            k = ptr[v + 1] - ptr[v]
            pred_ids[v, :k] = idx[ptr[v]:ptr[v + 1]]
            pred_b[v, :k] = byt[ptr[v]:ptr[v + 1]]
        sptr = graph.succ_indptr.tolist()
        S = int(np.diff(graph.succ_indptr).max())
        succ_ids = np.full((n + 1, S), n, dtype=np.int32)
        succ_b = np.zeros((n + 1, S))
        slot_of = {}
        for u in range(n):
            for s in range(sptr[u + 1] - sptr[u]):
                v = int(graph.succ_indices[sptr[u] + s])
                succ_ids[u, s] = v
                succ_b[u, s] = graph.succ_bytes[sptr[u] + s]
                slot_of[(u, v)] = s
        edge_slot = np.zeros((n + 1, D), dtype=np.int32)
        for v in range(n):
            for d in range(ptr[v + 1] - ptr[v]):
                edge_slot[v, d] = slot_of[(int(idx[ptr[v] + d]), v)]
        self.W, self.D, self.S = W, D, S

        cycles, energy, feas = cost_model.tables(graph)
        cyc_nc = np.zeros((n + 1, C))
        ecs_nc = np.zeros((n + 1, C))
        cyc_nc[:n] = np.where(feas, cycles, BIG)
        ecs_nc[:n] = np.where(feas, energy, BIG)
        layer = np.asarray(graph.layer)
        layer_pad = np.zeros(n + 1, dtype=np.int32)
        layer_pad[:n] = layer
        head = np.arange(n) == np.searchsorted(layer, layer)
        head_wb = np.where(head, np.asarray(hot["weight_bytes"]), 0.0)
        external = np.array([not wl.layers[int(l)].inputs for l in layer])
        ext_b = np.where(external, np.asarray(hot["new_in_bytes"], float), 0.)
        cc_per_byte = 8.0 / float(acc.dram_bw_bits_per_cc)
        d_ext = np.r_[ext_b * cc_per_byte, 0.0][wf]
        d_wt = np.r_[head_wb * cc_per_byte, 0.0][wf]
        tot = d_ext + d_wt
        pre = np.cumsum(tot, axis=1) - tot
        dram_off = np.maximum(np.where(d_ext > 0, pre + d_ext, NEG),
                              np.where(d_wt > 0, pre + tot, NEG))
        self.shared_l1 = acc.comm_style == "shared_mem"
        if self.shared_l1:
            act_cap = np.zeros(C)
            act_cap[0] = sum(c.act_mem_bytes for c in acc.cores)
            route_inv = np.zeros((C, C, 1))
            route_e = np.zeros((C, C))
        else:
            act_cap = np.array([c.act_mem_bytes for c in acc.cores], float)
            off = 1.0 - np.eye(C)
            route_inv = (off / float(acc.bus_bw_bits_per_cc))[:, :, None]
            route_e = off * float(acc.bus_energy_pj_per_bit)
        dram_bytes = float(head_wb.sum() + ext_b.sum())
        self.cc_per_byte = cc_per_byte
        self.e_per_byte = 8.0 * float(acc.dram_energy_pj_per_bit)
        self.e_const = dram_bytes * self.e_per_byte
        lvl_oh = np.zeros((n + 1, L))
        lvl_oh[np.arange(n), level] = 1.0
        self.t = {
            "wf": wf, "member": wf < n, "wf_pred": pred_ids[wf],
            "pred_ids": pred_ids, "pred_b": pred_b, "succ_ids": succ_ids,
            "succ_b": succ_b, "edge_slot": edge_slot,
            "out_bytes": np.r_[np.asarray(hot["out_bytes"], float), 0.0],
            "cyc_nc": cyc_nc, "ecs_nc": ecs_nc, "layer_pad": layer_pad,
            "dram_off": dram_off, "dram_tot": tot.sum(axis=1),
            "alloc_b": np.r_[np.asarray(hot["out_bytes"], float) + ext_b,
                             0.0][wf],
            "disc_b": np.r_[np.asarray(hot["disc_bytes"], float), 0.0][wf],
            "act_cap": act_cap, "route_inv": route_inv, "route_e": route_e,
            "layer_wb": np.array([l.weight_bytes for l in wl.layers.values()],
                                 float),
            "w_cap": np.array([c.weight_mem_bytes for c in acc.cores], float),
            "lvl_oh": lvl_oh,
        }
        self._fns = {}

    def scores(self, genomes, dtype: str = "float64") -> np.ndarray:
        """Approximate (K, 2) [latency, energy] of (K, G) genomes, computed
        in `dtype` on the host CPU."""
        import jax
        g = np.asarray(genomes, dtype=np.int32)
        with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
            if dtype not in self._fns:
                self._fns[dtype] = jax.jit(functools.partial(
                    self._score, dtype=dtype))
            lat, en = self._fns[dtype](g)
            return np.stack([np.asarray(lat, np.float64),
                             np.asarray(en, np.float64)], axis=1)

    def _segments(self, cores_gl, j, F):
        import jax
        import jax.numpy as jnp
        p = cores_gl.shape[0]
        rows = jnp.arange(p)

        def step(carry, x):
            acc_w, seg = carry
            core, wb = x
            cap = j["w_cap"][core]
            hold = jnp.minimum(wb, cap)
            held = jnp.take_along_axis(acc_w, core[:, None], axis=1)[:, 0]
            active = (wb > 0) & (cap > 0)
            cut = active & (held + hold > cap) & (held > 0)
            seg = seg + cut.astype(seg.dtype)
            acc_w = jnp.where(cut[:, None], 0.0, acc_w)
            acc_w = acc_w.at[rows, core].add(jnp.where(active, hold, 0.0))
            return (acc_w, seg), seg

        init = (jnp.zeros((p, self.C), F), jnp.zeros(p, jnp.int32))
        _, segs = jax.lax.scan(step, init, (cores_gl.T, j["layer_wb"]))
        return segs.T

    def _score(self, genomes, dtype):
        import jax
        import jax.numpy as jnp
        F = jnp.dtype(dtype)
        j = {k: jnp.asarray(v, F if v.dtype.kind == "f" else None)
             for k, v in self.t.items()}
        n, C = self.n, self.C
        p = genomes.shape[0]
        seg_gl = self._segments(genomes, j, F)
        core_ng = genomes.T[j["layer_pad"]]                  # (n+1, P)
        seg_ng = seg_gl.T[j["layer_pad"]]
        ids = jnp.arange(n + 1)[:, None]
        cyc_ng = j["cyc_nc"][ids, core_ng]
        ecs_ng = j["ecs_nc"][ids, core_ng]

        # fresh bytes: a producer ships to a core once; the first crossing
        # consumer on that core pays min(edge bytes, bytes left to ship)
        scr = core_ng[j["succ_ids"]]                         # (n+1, S, P)
        crossing = (j["succ_b"][:, :, None] > 0) & (scr != core_ng[:, None])
        tri = jnp.tril(jnp.ones((self.S, self.S), bool), k=-1)
        dup = ((scr[:, :, None] == scr[:, None, :]) & crossing[:, None]
               & tri[None, :, :, None])
        first = crossing & ~jnp.any(dup, axis=2)
        rem = jnp.broadcast_to(j["out_bytes"][:, None], core_ng.shape)
        cols = []
        for s in range(self.S):
            f = jnp.minimum(jnp.where(first[:, s], j["succ_b"][:, s, None],
                                      0.0), rem)
            rem = rem - f
            cols.append(f)
        fresh8 = 8.0 * jnp.stack(cols, axis=1)[j["pred_ids"], j["edge_slot"]]

        wf, member = j["wf"], j["member"]
        cyc_x, seg_x, cw_x = cyc_ng[wf], seg_ng[wf], core_ng[wf]
        on = ((cw_x[:, None] == jnp.arange(C)[None, :, None, None])
              & member[:, None, :, None])                    # (L, C, W, P)
        xs = {"wf": wf, "member": member, "cyc": cyc_x, "seg": seg_x,
              "dram": j["dram_off"], "tot": j["dram_tot"], "on": on,
              "pu": j["wf_pred"]}
        comm = not self.shared_l1
        pucn = core_ng[j["pred_ids"]]                        # (n+1, D, P)
        crossn = (j["pred_b"][:, :, None] > 0) & (pucn != core_ng[:, None])
        f8n = fresh8 * crossn
        if comm:
            occn = jnp.sum(f8n[..., None]
                           * j["route_inv"][pucn, core_ng[:, None]], axis=1)
            xs["cross"] = crossn[wf]                         # (L, W, D, P)
            xs["occ"] = jnp.moveaxis(occn, 2, 1)[wf].transpose(0, 2, 1, 3)
        aw = jnp.broadcast_to(j["alloc_b"][:, :, None], cyc_x.shape)
        fw = jnp.broadcast_to(j["disc_b"][:, :, None], cyc_x.shape)
        if comm:
            aw = aw + (jnp.sum(f8n, axis=1) / 8.0)[wf]
        aw = jnp.where(member[:, :, None], aw, 0.0)
        if self.shared_l1:
            onm = (member[:, None, :, None]
                   & (jnp.arange(C)[None, :, None, None] == 0))
            xs["mw"] = jnp.zeros_like(cw_x)
        else:
            onm = on
            xs["mw"] = cw_x
        xs["aw"] = aw
        xs["ac"] = jnp.sum(jnp.where(onm, aw[:, None], 0.0), axis=2)
        fc = jnp.sum(jnp.where(onm, fw[:, None], 0.0), axis=2)
        if comm:
            fbe = f8n / 8.0
            lvl_t = j["lvl_oh"].T
            fc = fc + jnp.stack([jnp.matmul(
                lvl_t, jnp.sum(jnp.where(pucn == c, fbe, 0.0), axis=1),
                precision=jax.lax.Precision.HIGHEST) for c in range(C)],
                axis=1)
        xs["fc"] = fc

        def step(state, x):
            (finish, core_free, chan_free, dram_free, seg_front, used,
             spilled, dram_x) = state
            pf = finish[x["pu"]]                             # (W, D, P)
            if comm:
                base = jnp.max(jnp.where(x["cross"], NEG, pf), axis=1,
                               initial=0.0)
                rel_b = jnp.max(jnp.where(x["cross"], pf, NEG), axis=1,
                                initial=NEG)
                occ = x["occ"]                               # (1, W, P)
                fin_ch, chan_free = fcfs(
                    chan_free, jnp.where(occ > 0, rel_b[None], NEG), occ)
                arr = jnp.max(jnp.where(occ > 0, fin_ch, NEG), axis=0)
                data_ready = jnp.maximum(base, arr)
            else:
                data_ready = jnp.max(pf, axis=1, initial=0.0)
            ready = jnp.maximum(data_ready,
                                dram_free[None] + x["dram"][:, None])
            dram_free = dram_free + x["tot"]
            fronts = seg_front
            k = 1
            while k < fronts.shape[0]:
                fronts = jnp.maximum(fronts, jnp.concatenate(
                    [jnp.full((k, p), NEG, F), fronts[:-k]], axis=0))
                k *= 2
            ex = jnp.concatenate([jnp.full((1, p), NEG, F), fronts[:-1]])
            ready = jnp.maximum(ready,
                                jnp.take_along_axis(ex, x["seg"], axis=0))
            mem = x["member"][:, None]
            on_core = x["on"]
            fin_c, core_free = fcfs(
                core_free, jnp.where(on_core, ready[None], NEG),
                jnp.where(on_core, x["cyc"][None], 0.0))
            fin_w = jnp.sum(jnp.where(on_core, fin_c, 0.0), axis=0)
            alloc_c = x["ac"]
            over = jnp.clip(used + alloc_c - j["act_cap"][:, None], 0.0,
                            alloc_c)
            frac = over / jnp.maximum(alloc_c, 1.0)
            frac_w = jnp.take_along_axis(frac, x["mw"], axis=0)
            spilled = spilled.at[x["wf"]].add(
                jnp.where(mem, x["aw"] * frac_w, 0.0))
            dram_x = dram_x + jnp.sum(over, axis=0)
            used = jnp.maximum(jnp.minimum(used + alloc_c - over,
                                           j["act_cap"][:, None])
                               - x["fc"], 0.0)
            finish = finish.at[x["wf"]].set(fin_w)
            seg_front = seg_front.at[x["seg"], jnp.arange(p)[None]].max(
                jnp.where(mem, fin_w, NEG))
            return (finish, core_free, chan_free, dram_free, seg_front,
                    used, spilled, dram_x), None

        z = functools.partial(jnp.zeros, dtype=F)
        state = (z((n + 1, p)), z((C, p)), z((1, p)), z(p),
                 z((self.n_layers, p)), z((C, p)), z((n + 1, p)), z(p))
        (finish, _, chan_free, dram_free, _, _, spilled, dram_x), _ = \
            jax.lax.scan(step, state, xs)
        dram_x = dram_x + jnp.sum(jnp.minimum(
            spilled[j["pred_ids"]], j["pred_b"][:, :, None]), axis=(0, 1))
        latency = jnp.maximum(jnp.max(finish, axis=0),
                              dram_free + dram_x * self.cc_per_byte)
        latency = jnp.maximum(latency, jnp.max(chan_free, axis=0))
        energy = (jnp.sum(ecs_ng[:n], axis=0) + self.e_const
                  + dram_x * self.e_per_byte)
        if comm:
            energy = energy + jnp.sum(
                f8n * j["route_e"][pucn, core_ng[:, None]], axis=(0, 1))
        return latency, energy
