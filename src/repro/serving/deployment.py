"""Multi-region PrfaaS-PD deployment, in-process, sharing ONE control plane
with the cluster simulator.

Topology (``DeploymentConfig.pd_clusters`` = N regions):

  * "PrfaaS cluster"   — a shared ``PrefillEngine`` (long requests, l > t)
                         with its own ``HybridPrefixCache``
  * N "PD regions"     — each with its own ``DecodeEngine``,
                         ``HybridPrefixCache``, and ``RegionScheduler``
                         (local prefill runs on a shared PD
                         ``PrefillEngine``: in-process the compute is
                         identical, the policy state is per-region).
                         Routed requests feed the home region's scheduler
                         immediately; each scheduler tick interleaves one
                         prefill unit (bucket batch or long-prompt chunk)
                         with one decode block, admitting finished prefills
                         at block boundaries — no drain-and-re-admit batch
                         loop, no decode idle while prefill runs.  Every
                         finished unit passes through ``_unit_done``, which
                         keeps the wire/TTFT/truncation accounting of the
                         old batch loop at unit granularity.
  * inter-DC links     — a ``core.transfer.LinkTopology``: one exact
                         fair-share ``Link`` per PrfaaS<->region star pair,
                         plus an optional PD<->PD mesh for cross-region
                         cache copies.  Byte accounting uses the same
                         virtual-clock flow solver as the simulator.

The deployment contains NO routing policy of its own.  Route choice, cache
placement, and threshold adaptation all go through ``core.router.Router``:
each request's per-cluster prefix matches and its home pair-link telemetry
are handed to ``Router.route(l, matches, signal, home=)``, and after every
batch each region's aggregated congestion view (``LinkTopology.dest_signal``)
is fed back through ``Router.observe_congestion(signal, home=)`` so per-home
thresholds adapt during a live run — exactly the short-term loop the
simulator runs.  ``launch.serve --cross-validate`` replays a live run's
arrival trace through ``core.simulator.PrfaasSimulator`` and checks the two
agree per request.

int8 KV on the wire (``DeploymentConfig.wire_compression``): the quantized
pytree from ``models.kvcache.quantize_cache_for_wire`` is what actually
crosses the links — flow bytes are measured from the quantized leaves, and
the cache is dequantized before decode admission.  The running
quantized/raw ratio (``measured_compression``) is the value
``SystemConfig.kv_wire_compression`` should carry in the analytic model and
the simulator.

Cache metadata goes through one ``core.kv_manager.GlobalKVManager``: every
cluster cache registers there, ``_route`` reads its per-cluster matches
(restricted to link-reachable clusters), and finished prefills record
through it — so hotspot rebalancing and its ``rebalanced`` /
``cross_transfers`` counters observe live traffic exactly as they observe
the simulator's.

Device prefix reuse (``DeploymentConfig.paged_kv``): each PD region's
``DecodeEngine`` runs the paged layout, sharing ONE ``BlockPool`` with the
region's ``HybridPrefixCache`` — prompt pages register at admission
(``insert_device``) and stay LRU-resident after the request retires.  A
locally-prefilled request whose prefix matches resumes from those pages:
``match_resume`` pins them (ref-counts) and the scheduler prefills only
the uncached suffix, so a prefix hit skips the cached-prefix compute
instead of recomputing and reshipping it.  Offloaded (PrfaaS) requests
still ship the full cache — the prefill ran in another datacenter, where
the home region's device pages don't exist — so live egress upper-bounds
the simulator's incremental ``S_kv(total) - S_kv(cached)`` charge on that
path, while the local path now matches it.  With ``paged_kv=False`` (the
default) the dense per-slot layout and the byte-accounting-twin pools are
bit-identical to the pre-paged deployment.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import AttentionSpec
from repro.core.blockpool import BlockPool
from repro.core.hardware import CHIPS, AnalyticProfile
from repro.core.kv_manager import GlobalKVManager
from repro.core.prefix_cache import HybridPrefixCache
from repro.core.router import PD, PRFAAS, Router, RouterConfig, RoutingDecision
from repro.core.throughput_model import SystemConfig, ThroughputModel
from repro.core.transfer import Link, LinkTopology, star_pairs
from repro.core.workload import Workload
from repro.models import Model, paged_layout
from repro.models.kvcache import (cache_num_bytes, dequantize_cache_from_wire,
                                  kv_bytes, quantize_cache_for_wire)
from repro.serving.api import PagePin, Request, Response
from repro.serving.engine import (DecodeEngine, PrefillEngine,
                                  RegionScheduler, trim_request_cache)
from repro.serving.spans import span


@dataclass
class DeploymentConfig:
    threshold: int = 256               # base routing threshold t (tokens)
    link_gbps: float = 1.0             # PrfaaS->region star links (shared)
    pd_link_gbps: Optional[Tuple[float, ...]] = None  # per-region override
    pd_mesh_gbps: float = 0.0          # PD<->PD links (0 = star only)
    pd_clusters: int = 1               # regional PD clusters
    decode_slots: int = 8
    capacity: int = 2048               # decode KV capacity per slot
    decode_block_size: int = 8         # tokens per on-device decode block
    min_prefill_bucket: int = 32       # smallest pow2 prefill length bucket
    max_prefill_bucket: Optional[int] = None  # chunked prefill past this
    max_prefill_batch: int = 8         # requests per scheduler prefill unit
    temperature: float = 0.0           # 0 = greedy (bit-identical default)
    top_k: int = 0                     # 0 = full vocab when sampling
    sample_seed: int = 0               # decode sampling PRNG seed
    spec_k: int = 0                    # speculative draft tokens per round
    spec_ngram: int = 2                # drafter suffix-match length
    tbt_slo_s: float = 0.0             # TBT SLO for attainment (0 = off)
    block_tokens: int = 16
    pool_blocks: int = 4096
    # paged device KV: region decode engines use BlockPool pages as the
    # real cache layout and resume prefix hits from registered pages
    # (suffix-only prefill); False keeps the dense per-slot layout with the
    # pools as byte-accounting twins (bit-identical legacy behavior)
    paged_kv: bool = False
    layerwise_pipeline: bool = True
    wire_compression: bool = False     # int8 KV quantization on the wire
    adapt_thresholds: bool = True      # live per-home congestion feedback
    chip: str = "h200"                 # AnalyticProfile chip for the Router
    chips_per_instance: int = 8
    # path to a BENCH_kernel.json written by benchmarks.kernel_bench: the
    # Router's profile (thresholds, S_kv/T_prefill trade-off) then derives
    # from THIS machine's measured kernels (analysis.calibrate) instead of
    # the named chip's roofline
    calibration: Optional[str] = None


class CrossDCDeployment:
    def __init__(self, model: Model, params, cfg: DeploymentConfig,
                 prfaas_model: Optional[Model] = None,
                 prfaas_params=None,
                 router_cfg: Optional[RouterConfig] = None):
        self.model = model
        self.cfg = cfg
        k = cfg.pd_clusters
        if k < 1:
            raise ValueError("pd_clusters must be >= 1")
        # region naming matches the simulator: the classic two-cluster
        # deployment keeps the legacy "pd" name
        self.pd_names = [PD] if k == 1 else [f"pd{i}" for i in range(k)]
        bucket_kw = dict(min_bucket=cfg.min_prefill_bucket,
                         max_bucket=cfg.max_prefill_bucket)
        self.prfaas = PrefillEngine(prfaas_model or model,
                                    prfaas_params if prfaas_params is not None
                                    else params, **bucket_kw)
        self.pd_prefill = PrefillEngine(model, params, **bucket_kw)
        # paged regions share ONE BlockPool between the decode engine (page
        # storage) and the region's prefix cache (page index): a cache hit
        # names real device pages
        pools: Dict[str, BlockPool] = {}
        if cfg.paged_kv:
            for name in self.pd_names:
                pools[name] = BlockPool(cfg.pool_blocks, cfg.block_tokens,
                                        1 << 16)
        self.decoders: Dict[str, DecodeEngine] = {
            name: DecodeEngine(model, params, cfg.decode_slots, cfg.capacity,
                               block_size=cfg.decode_block_size,
                               temperature=cfg.temperature, top_k=cfg.top_k,
                               seed=cfg.sample_seed, paged=cfg.paged_kv,
                               pool=pools.get(name),
                               page_tokens=cfg.block_tokens,
                               spec_k=cfg.spec_k, spec_ngram=cfg.spec_ngram)
            for name in self.pd_names}
        # one continuously-batched scheduler loop per region: it owns the
        # region's prefill queue and decode slots together; every finished
        # unit flows through _unit_done for wire/metrics accounting
        self.schedulers: Dict[str, RegionScheduler] = {
            name: RegionScheduler(self.pd_prefill, self.decoders[name],
                                  max_prefill_batch=cfg.max_prefill_batch,
                                  on_unit_done=self._unit_done)
            for name in self.pd_names}
        self.caches: Dict[str, HybridPrefixCache] = {PRFAAS: self._new_cache()}
        for name in self.pd_names:
            if cfg.paged_kv:
                self.caches[name] = self._paged_cache(pools[name])
                self._wire_admission(name)
            else:
                self.caches[name] = self._new_cache()
        # all cache metadata flows through the global manager: per-cluster
        # matching for routing, prefill registration, hotspot rebalancing
        self.kv = GlobalKVManager()
        for name, cache in self.caches.items():
            self.kv.register_cluster(name, cache)

        # ------- shared control plane: the simulator's Router + topology ---
        star = (list(cfg.pd_link_gbps) if cfg.pd_link_gbps is not None
                else [cfg.link_gbps] * k)
        if len(star) != k:
            raise ValueError("pd_link_gbps must have one entry per region")
        if cfg.calibration:
            from repro.analysis.calibrate import (calibrated_profile,
                                                  load_calibration)
            profile = calibrated_profile(model.cfg,
                                         load_calibration(cfg.calibration),
                                         cfg.chips_per_instance)
        else:
            profile = AnalyticProfile(model.cfg, CHIPS[cfg.chip],
                                      cfg.chips_per_instance)
        self.profile = profile
        self.throughput_model = ThroughputModel(profile, profile, Workload())
        self.system = SystemConfig(1, k, k, sum(star) * 1e9 / 8.0,
                                   float(cfg.threshold))
        self.router = Router(self.throughput_model, self.system, router_cfg)
        pairs = star_pairs(PRFAAS, self.pd_names,
                           mesh=cfg.pd_mesh_gbps > 0 and k > 1)
        gbps = star + [cfg.pd_mesh_gbps] * (len(pairs) - k)
        self.topology = LinkTopology.build([PRFAAS] + self.pd_names, pairs,
                                           gbps)

        self.completed: List[Request] = []
        self.virtual_now = 0.0
        self._wire_raw = 0.0           # raw bytes of caches put on the wire
        self._wire_quant = 0.0         # their measured quantized bytes
        self._seed_ratio = 1.0         # dry-run ratio used before any flow
        if cfg.wire_compression:
            # seed the measured ratio from a one-page dry-run quantization
            # so measured_compression() reflects the configured wire format
            # from construction instead of reporting 1.0 until the first
            # quantized flow ships.  The seed is kept OUT of the running
            # accumulators: once real flows exist the ratio is exactly
            # theirs, not skewed by the probe.
            from repro.models.paged import zero_request_payload
            probe = zero_request_payload(model.cfg, cfg.block_tokens)
            self._seed_ratio = (float(cache_num_bytes(probe))
                                / float(quantize_cache_for_wire(probe)[1]))

    def _new_cache(self) -> HybridPrefixCache:
        return HybridPrefixCache(
            BlockPool(self.cfg.pool_blocks, self.cfg.block_tokens, 1 << 16),
            0, 1)

    def _paged_cache(self, pool: BlockPool) -> HybridPrefixCache:
        """Region prefix cache sharing the decode engine's page pool: its
        entries are registered at admission (``insert_device``) and name
        live device pages, so a match is device-resumable."""
        lay = paged_layout(self.model.cfg, self.cfg.capacity,
                           self.cfg.block_tokens, 1)
        has_state = any(not isinstance(b.mixer, AttentionSpec)
                        for g in self.model.cfg.groups for b in g.blocks)
        return HybridPrefixCache(pool, 0, 1,
                                 has_full_attn=lay.seq_cols > 0,
                                 has_linear=lay.ring_cols > 0 or has_state)

    def _wire_admission(self, name: str):
        cache, dec = self.caches[name], self.decoders[name]
        dec.on_admit = lambda req, L, ids, snap: cache.insert_device(
            [int(t) for t in req.tokens], ids, snap)
        # offloaded prefills arriving as int8 wire pytrees admit AS wire:
        # dequantization fuses into the page scatter instead of a separate
        # full-cache pass on the admission path
        dec.wire_admission = bool(self.cfg.wire_compression)

    # ------------------------------------------------- two-cluster aliases
    @property
    def link(self) -> Link:
        """First region's star link (the classic single inter-DC link)."""
        return self.topology.link(PRFAAS, self.pd_names[0])

    @property
    def decode(self) -> DecodeEngine:
        return self.decoders[self.pd_names[0]]

    # ------------------------------------------------------------- routing
    def _route(self, req: Request) -> RoutingDecision:
        with span("prfaas.route"):
            home = req.home or self.pd_names[0]
            if home not in self.pd_names:
                raise ValueError(f"unknown home region {home!r}; "
                                 f"expected one of {self.pd_names}")
            req.home = home
            toks = list(map(int, req.tokens))
            matches = self.kv.match_all(
                toks, names=[n for n in self.caches
                             if self.topology.cache_reachable(home, n,
                                                              hub=PRFAAS)])
            decision = self.router.route(
                len(toks), matches, self.topology.pair_signal(PRFAAS, home),
                home=home)
            req.decision = decision
            req.route = decision.target
            req.cached_tokens = decision.cached_tokens
            if self.cfg.paged_kv and decision.target == home:
                # local prefill on a paged region: pin the device-resident
                # prefix pages (ref-counts transfer to the engine at
                # admission) so only the uncached suffix is computed.  An
                # offloaded prefill cannot use home device pages — it ships
                # the full cache as before.
                c, ids, snap = self.caches[home].match_resume(toks)
                if c:
                    self.decoders[home].pool.retain(ids)
                    req.device_pin = PagePin(c, ids, snap)
            return decision

    # ------------------------------------------------------------ lifecycle
    def _unit_done(self, engine: PrefillEngine, rs: List[Request], lengths,
                   first, caches, wall: float) -> list:
        """Per-unit accounting hook the region schedulers call when a
        prefill unit (bucketed batch or chunked prompt) finishes: trim to
        true lengths, quantize + submit wire flows, insert prefix-cache
        entries, compute transfer exposure and TTFT — exactly the
        accounting the old per-cluster batch loop did, at unit granularity.
        Returns the decode admit entries for the scheduler's ready queue.
        An offloaded request's quantize, link submit and dequantize run in
        one ``prfaas.wire`` span."""
        with span("prfaas.unit_done"):
            self.topology.advance(self.virtual_now)      # sync link clocks
            flows: Dict[int, list] = {}
            entries = []
            for i, r in enumerate(rs):
                cluster = r.decision.target
                r.prefill_s = wall
                # trim to the request's true length: bucket padding must not
                # inflate wire bytes (or corrupt SWA ring placement)
                payload = trim_request_cache(caches, i, len(r.tokens))
                r.kv_bytes_raw = cache_num_bytes(payload)
                r.transfer_s = 0.0
                fl = []
                if cluster == PRFAAS:
                    with span("prfaas.wire", rid=r.rid) as sp:
                        payload, flow = self._wire(r, payload, wall)
                        sp.attrs["bytes"] = int(r.kv_bytes)
                    fl.append(("kv", PRFAAS, r.home, flow))
                else:
                    r.kv_bytes = r.kv_bytes_raw          # intra-cluster RDMA
                d = r.decision
                if d.cross_cache_transfer and d.cached_tokens:
                    # cached prefix lives in another cluster: the copy is
                    # already materialized (eager flow), charged to the
                    # owner<->target pair link, compressed like the rest of
                    # the wire traffic
                    nb = float(kv_bytes(self.model.cfg, d.cached_tokens))
                    if self.cfg.wire_compression:
                        nb /= self.measured_compression()
                    nb = max(nb, 1.0)
                    r.cross_kv_bytes = nb
                    fl.append(("copy", d.cache_cluster, d.target,
                               self.topology.submit(
                                   d.cache_cluster, d.target, nb,
                                   self.virtual_now,
                                   ramp_end=self.virtual_now)))
                flows[r.rid] = fl
                if not (self.cfg.paged_kv and cluster != PRFAAS):
                    # paged regions register their device pages at
                    # ADMISSION (insert_device): inserting metadata blocks
                    # here would bind prefix hashes to pageless entries that
                    # match_resume would hand back as if they held KV
                    self.kv.record_prefill(cluster, list(map(int, r.tokens)))
                entries.append((r, int(first[i]), payload, len(r.tokens)))
            if any(flows.values()):
                self.topology.run_until_idle()
            for r in rs:
                exposure = 0.0
                for kind, a, b, f in flows.get(r.rid, ()):
                    tail = 0.0
                    if kind == "kv":
                        # the pipelined prefill KV's last layer can never
                        # overlap its own compute (eager "copy" flows are
                        # already materialized: no serial tail)
                        floor = 1.0 / max(1, self.model.cfg.n_layers)
                        tail = f.total_bytes * floor \
                            / self.topology.link(a, b).current_capacity()
                    exposed = f.done_time - (self.virtual_now + wall)
                    exposure = max(exposure, exposed, tail)
                if flows.get(r.rid):
                    r.transfer_s = max(exposure, 0.0)
                r.ttft_s = r.prefill_s + r.transfer_s
            self.virtual_now += wall
            return entries

    def _wire(self, r: Request, payload, wall: float):
        """Ship an offloaded request's trimmed cache over its home's link:
        quantize (int8 wire), submit the flow, and dequantize again where
        the home's admission needs the dense pytree.  Returns (the payload
        to admit, the flow)."""
        if self.cfg.wire_compression:
            # the quantized pytree IS what crosses the link: bytes come from
            # the quantized leaves, and the cache is dequantized before
            # decode admission
            payload, nbytes = quantize_cache_for_wire(payload)
            self._wire_raw += r.kv_bytes_raw
            self._wire_quant += nbytes
        else:
            nbytes = r.kv_bytes_raw
        r.kv_bytes = nbytes
        # layer-wise pipelined: KV becomes wire-eligible as prefill computes
        # (linear ramp over the prefill); unpipelined: the flow only starts
        # once prefill ends.  Either way the unit's flows contend on the
        # exact fair-share pair link solver.
        start = (self.virtual_now if self.cfg.layerwise_pipeline
                 else self.virtual_now + wall)
        flow = self.topology.submit(PRFAAS, r.home, max(float(nbytes), 1.0),
                                    start, ramp_end=self.virtual_now + wall)
        if self.cfg.wire_compression and not getattr(
                self.decoders[r.home], "wire_admission", False):
            # dense admission needs the dense pytree back; paged homes with
            # wire admission dequantize inside the page scatter
            payload = dequantize_cache_from_wire(payload)
        return payload, flow

    def submit_batch(self, reqs: List[Request]) -> Dict[int, Response]:
        """Serve a batch of requests end-to-end; returns responses.

        Requests feed their home region's ``RegionScheduler`` as they
        route; the scheduler loops then run concurrently (round-robin
        ticks, in-process) — prefill units interleave with decode blocks
        and admission happens at block boundaries, never by draining a
        region to empty first."""
        for r in reqs:
            decision = self._route(r)
            engine = (self.prfaas if decision.target == PRFAAS
                      else self.pd_prefill)
            self.schedulers[r.home].submit(r, engine)

        scheds = list(self.schedulers.values())
        while any(s.has_work for s in scheds):
            for s in scheds:
                if s.has_work:
                    s.tick()

        # live short-term loop: every region feeds its OWN aggregated
        # congestion view back into the shared Router, adapting that home's
        # threshold alone — identical to the simulator's control epoch
        if self.cfg.adapt_thresholds:
            for name in self.pd_names:
                self.router.observe_congestion(
                    self.topology.dest_signal(name), home=name)

        out: Dict[int, Response] = {}
        for dec in self.decoders.values():
            out.update(dec.outputs)
        self.completed.extend(reqs)
        return out

    # -------------------------------------------------------------- metrics
    def measured_compression(self) -> float:
        """Running measured raw/quantized byte ratio of the KV put on the
        wire.  With ``wire_compression`` enabled the ratio is seeded at
        construction from a one-page dry-run quantization, so it reflects
        the wire format immediately; live flows then dominate the running
        ratio.  Without compression (nothing ever quantized) it is 1.0."""
        if self._wire_quant > 0:
            return self._wire_raw / self._wire_quant
        return self._seed_ratio

    @staticmethod
    def _tbt_stats(tbt: List[float], slo_s: float) -> dict:
        """Measured per-request mean time-between-tokens: percentiles plus
        SLO attainment (fraction of requests at/under ``slo_s``; 1.0 when
        the SLO is unset or nothing finished yet)."""
        if not tbt:
            return {"tbt_mean_s": 0.0, "tbt_p50_s": 0.0, "tbt_p90_s": 0.0,
                    "tbt_p99_s": 0.0, "tbt_slo_s": slo_s,
                    "tbt_attainment": 1.0}
        arr = np.asarray(tbt)
        return {
            "tbt_mean_s": float(arr.mean()),
            "tbt_p50_s": float(np.percentile(arr, 50)),
            "tbt_p90_s": float(np.percentile(arr, 90)),
            "tbt_p99_s": float(np.percentile(arr, 99)),
            "tbt_slo_s": slo_s,
            "tbt_attainment": (float((arr <= slo_s).mean())
                               if slo_s > 0 else 1.0),
        }

    @staticmethod
    def _latency_means(rs: List[Request]) -> dict:
        """Mean wall TTFT (submit -> first token on the host, both stamped
        on the span clock) and, apart from it, the mean virtual-clock link
        exposure of the same requests."""
        ttft = [r.t_first - r.t_submit for r in rs
                if r.t_first is not None and r.t_submit is not None]
        return {"ttft_mean_s": float(np.mean(ttft)) if ttft else 0.0,
                "link_exposed_s_mean": (float(np.mean([r.transfer_s
                                                       for r in rs]))
                                        if rs else 0.0)}

    def metrics(self) -> dict:
        done = self.completed
        per_region = {}
        for name in self.pd_names:
            rs = [r for r in done if r.home == name]
            dec = self.decoders[name]
            per_region[name] = {
                "requests": len(rs),
                "offloaded": sum(1 for r in rs if r.route == PRFAAS),
                **self._latency_means(rs),
                "threshold": self.router.threshold_for(name),
                "cache_hit_rate": self.caches[name].hit_rate(),
                "truncations": self.decoders[name].truncations,
                "occupancy": self.schedulers[name].occupancy(),
                "goodput_tok_s": self.schedulers[name].goodput_tok_s(),
                "max_admit_wait": self.schedulers[name].max_admit_wait,
                "accepted_tokens_per_dispatch":
                    dec.accepted_tokens_per_dispatch,
                # share of the KV capacity the dense decode-attention
                # kernel copied from HBM (None: no dense block ran)
                "decode_kv_fetch_share": (
                    dec.kv_tiles_fetched / dec.kv_tiles_capacity
                    if dec.kv_tiles_capacity else None),
                **self._tbt_stats(dec.tbt_s, self.cfg.tbt_slo_s),
            }
            if self.cfg.paged_kv:
                dec = self.decoders[name]
                pool = dec.pool
                per_region[name]["pool"] = {
                    **pool.stats, "resident": pool.resident,
                    "used_blocks": pool.used_blocks,
                    "num_blocks": pool.num_blocks}
                # headroom: device bytes held by LRU-resident prefix pages
                # (reclaimable on demand, reusable on a hit)
                per_region[name]["resident_kv_bytes"] = \
                    pool.resident * dec.page_bytes
                per_region[name]["page_fail_retires"] = dec.page_fail_retires
        busy = sum(d.slot_busy_s for d in self.decoders.values())
        span = sum(self.cfg.decode_slots * s.wall_s
                   for s in self.schedulers.values())
        all_tbt = [t for d in self.decoders.values() for t in d.tbt_s]
        rounds = sum(d.verify_rounds for d in self.decoders.values())
        accepted = sum(d.accepted_tokens for d in self.decoders.values())
        return {
            "requests": len(done),
            "offloaded": sum(1 for r in done if r.route == PRFAAS),
            **self._latency_means(done),
            "kv_bytes_total": sum(r.kv_bytes for r in done
                                  if r.route == PRFAAS),
            "cache_hit_rate": {k: c.hit_rate()
                               for k, c in self.caches.items()},
            "thresholds": {n: self.router.threshold_for(n)
                           for n in self.pd_names},
            "router_decisions": dict(self.router.decisions),
            "cross_transfers": self.router.cross_transfers,
            "kv_manager": {"rebalanced": self.kv.rebalanced,
                           "cross_transfers": self.kv.cross_transfers,
                           "clusters": self.kv.stats()},
            "paged_kv": self.cfg.paged_kv,
            "truncations": sum(d.truncations for d in self.decoders.values()),
            "occupancy": busy / span if span > 0 else 0.0,
            "goodput_tok_s": sum(s.goodput_tok_s()
                                 for s in self.schedulers.values()),
            "accepted_tokens_per_dispatch": (accepted / rounds if rounds
                                             else 1.0),
            **self._tbt_stats(all_tbt, self.cfg.tbt_slo_s),
            "wire_compression": self.measured_compression(),
            "clusters": per_region,
            "links": self.topology.pair_stats(),
        }
