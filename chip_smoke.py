#!/usr/bin/env python3
"""Smoke run of the served path on one TPU chip, with the Pallas kernels.

    python chip_smoke.py [--seed 0]

Builds zamba2-1.2b at its published widths with random parameters made
from ``--seed`` (nothing is downloaded), then, in one process:

1. checks every op of ``repro.kernels.ops`` that the served path reaches
   against its jnp reference on the chip (``kernel_vs_ref`` lines);
2. compares the prefill logits of one 1,024-token prompt between
   ``Model(cfg)`` (kernels) and ``Model(cfg, use_kernels=False)`` on the
   same parameters, each against the same model in float32
   (``logits_vs_ref``);
3. serves two turns of requests through ``serving.CrossDCDeployment`` —
   routing, bucketed and chunked prefill, the int8 wire, admission and
   decode — once in the dense cache layout and once with ``paged_kv=True``
   (``serve[...]`` lines), and checks what came back.

It refuses to run on anything but a TPU.  Every check runs; if any fails
the script prints why and exits 1 before the last line.  The last line of
standard output is one JSON object naming the device.  Times printed here
describe this smoke run (compilation included); none is a benchmark number.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "zamba2-1.2b"
MAX_NEW = 16
# turn 1: three prompts at or under THRESHOLD prefill in the home region, five
# over it are offloaded to PrfaaS; the three past MAX_BUCKET run as chunked
# prefill (2, 3 and 4 chunks)
TURN1 = (64, 100, 128, 700, 900, 1792, 2600, 3500)
THRESHOLD = 512
MAX_BUCKET = 1024
CAPACITY = 4096
# turn 2 grows one local and one offloaded turn-1 prompt: prefix hits.  A
# hybrid model resumes from device pages only where a Mamba2 state snapshot
# exists, and one is kept only for prompts a whole number of 16-token pages
# long, hence 128 and 1792 above
GROW = {2: 100, 5: 150}
# One chip's share: the dense decode cache and the paged pool each hold
# 16K tokens (4 slots x 4096, 1024 pages x 16).  Their (Hkv=32, D=64) minor
# dims pad 2x in HBM, and a decode block keeps several cache-sized
# temporaries, so 8 slots would put one program at ~12 of the 16 GB.
DECODE_SLOTS = 4
POOL_BLOCKS = 1024
# kernel vs reference, per op: largest |kernel - ref| over largest |ref|.
# Inputs and outputs are bf16 (unit roundoff 2^-9 ~ 2e-3); both sides
# accumulate in f32 and round the same tensors to bf16, so they may differ
# by a few output roundoffs, never by more than 10 (the bound the CPU
# dispatch tests hold bf16 to).  The reference runs at highest matmul
# precision, so it is the exact-f32 oracle.
KERNEL_TOL = 2e-2
# logits: relative L2 distance from the exact model (the same parameters in
# float32 at highest matmul precision).  The reference and the kernel path
# share every bf16 tensor and differ only in how f32 intermediates round, so
# both carry the same bf16 rounding error against the exact model; the
# kernels may add rounding noise of their own.  Independent noises add in
# quadrature, so kernel noise up to 3/4 of the reference's own distance
# keeps the kernel path within sqrt(1 + 0.75^2) = 1.25x of it.  A kernel
# bug (wrong mask, decay or state) decorrelates the logits and lands far
# outside.  (Random weights make a 38-block model amplify any rounding
# difference with depth, so kernel-vs-reference alone has no fixed bound.)
LOGITS_TOL = 1.25


class Checks:
    def __init__(self):
        self.failed = []

    def expect(self, ok: bool, what: str):
        if not ok:
            self.failed.append(what)
            print(f"CHECK FAILED: {what}", file=sys.stderr, flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the number of
    backend compiles (persistent-cache hits are not compiles)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
            self.compiles += event == self.EVENTS[-1]


def rel_max(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def rel_l2(a, b) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ----------------------------------------------------------------- kernels


def kernel_cases(cfg, seed):
    """(name, op, args) cases, run as ``op(*args, use_kernel=...)``, at the
    widths ``cfg`` serves with: its attention and Mamba2 mixer, the decode
    cache and page pool the deployment below uses, and the int8 wire leaf
    of a 3.5K prompt.  ``delta`` (no zamba2 layer uses it) runs at
    kimi-linear-1t's KDA widths."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.configs.base import AttentionSpec, LinearSpec
    from repro.kernels import ops

    blocks = [b.mixer for g in cfg.groups for b in g.blocks]
    att = next(m for m in blocks if isinstance(m, AttentionSpec))
    lin = next(m for m in blocks if isinstance(m, LinearSpec))
    kda = next(b.mixer for g in get_config("kimi-linear-1t").groups
               for b in g.blocks if isinstance(b.mixer, LinearSpec))
    H, Hkv, D = att.q_heads, att.kv_heads, att.head_dim
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def rnd(*shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    S, B, T = 1024, DECODE_SLOTS, 16
    lens = jnp.asarray(np.linspace(1, CAPACITY, B).astype(np.int32))
    dec_q, dec_k, dec_v = rnd(B, H, D), rnd(B, Hkv, CAPACITY, D), \
        rnd(B, Hkv, CAPACITY, D)
    pages = CAPACITY // T
    pk, pv = rnd(Hkv, B * pages + 1, T, D), rnd(Hkv, B * pages + 1, T, D)
    tables = jax.random.permutation(next(keys), B * pages).reshape(
        B, pages).astype(jnp.int32)
    q, k, v = rnd(1, H, S, D), rnd(1, Hkv, S, D), rnd(1, Hkv, S, D)
    C = 128                                     # suffix chunk over 1K prior
    suf_q, suf_k, suf_v = rnd(1, H, C, D), rnd(1, Hkv, C, D), \
        rnd(1, Hkv, C, D)
    vq = rnd(B, H, 3, D)
    lg = jnp.asarray([S, 700], jnp.int32)
    gq, gk = rnd(2, lin.heads, S, lin.key_dim), rnd(2, lin.heads, S,
                                                     lin.key_dim)
    gv = rnd(2, lin.heads, S, lin.value_dim)
    g_la = -jnp.abs(rnd(2, lin.heads, S, dtype=jnp.float32))
    g_s0 = rnd(2, lin.heads, lin.key_dim, lin.value_dim, dtype=jnp.float32)

    def unit(x):
        return (x / jnp.linalg.norm(x.astype(jnp.float32), axis=-1,
                                    keepdims=True)).astype(bf)

    dq, dk = unit(rnd(1, kda.heads, S, kda.key_dim)), \
        unit(rnd(1, kda.heads, S, kda.key_dim))
    dv = rnd(1, kda.heads, S, kda.value_dim)
    d_la = -0.1 * jnp.abs(rnd(1, kda.heads, S, dtype=jnp.float32))
    d_beta = jax.nn.sigmoid(rnd(1, kda.heads, S, dtype=jnp.float32))
    wire = rnd(6, 1, 3500, Hkv, D, dtype=jnp.float32)
    verify_lens = jnp.minimum(lens, CAPACITY - 3)
    prior = tables[:1, :S // T]
    return [
        ("attention", ops.attention, (q, k, v)),
        ("decode_attention", ops.decode_attention,
         (dec_q, dec_k, dec_v, lens)),
        ("verify_attention", ops.verify_attention,
         (vq, dec_k, dec_v, verify_lens)),
        ("paged_decode_attention", ops.paged_decode_attention,
         (dec_q, pk, pv, tables, lens)),
        ("paged_prefill_attention", ops.paged_prefill_attention,
         (suf_q, pk, pv, prior, suf_k, suf_v)),
        ("gla", lambda *a, use_kernel: ops.gla(*a[:5], lengths=a[5],
                                               use_kernel=use_kernel),
         (gq, gk, gv, g_la, g_s0, lg)),
        ("delta", lambda *a, use_kernel: ops.delta(*a[:5], lengths=a[5],
                                                   use_kernel=use_kernel),
         (dq, dk, dv, d_la, d_beta, lg[:1])),
        ("quantize_wire", ops.quantize_wire, (wire,)),
    ]


def check_kernels(cfg, seed, checks):
    import jax

    for name, op, args in kernel_cases(cfg, seed):
        kernel = jax.jit(lambda *a: op(*a, use_kernel=True))
        ref = jax.jit(lambda *a: op(*a, use_kernel=False))
        checks.expect("tpu_custom_call" in kernel.lower(*args).as_text(),
                      f"{name}: no Pallas kernel in the compiled op")
        got = jax.block_until_ready(kernel(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(ref(*args))
        got, want = jax.tree.leaves(got), jax.tree.leaves(want)
        if name == "quantize_wire":
            # int8 codes: a 1-ulp difference in x / scale can move a value
            # across a rounding tie, one code and never more
            codes = np.abs(np.asarray(got[0], np.int32)
                           - np.asarray(want[0], np.int32))
            same_scale = bool(np.asarray(got[1]) == np.asarray(want[1]))
            print(f"kernel_vs_ref: {name} codes_differing="
                  f"{int((codes > 0).sum())}/{codes.size} max_code_diff="
                  f"{int(codes.max())} scale_identical={same_scale}",
                  flush=True)
            checks.expect(codes.max() <= 1 and same_scale,
                          f"{name}: codes differ by >1 or scale differs")
            continue
        gaps = [rel_max(g, w) for g, w in zip(got, want)]
        finite = all(np.isfinite(np.asarray(g, np.float32)).all()
                     for g in got)
        print(f"kernel_vs_ref: {name} rel_max_gap="
              + ",".join(f"{x:.3e}" for x in gaps)
              + f" tol={KERNEL_TOL} finite={finite}", flush=True)
        checks.expect(finite and max(gaps) <= KERNEL_TOL,
                      f"{name}: gap {max(gaps):.3e} > {KERNEL_TOL} or "
                      "non-finite")


# ------------------------------------------------------------------ logits


def check_logits(cfg, params, seed, checks):
    """Prefill logits of one 1,024-token prompt: kernels vs reference, and
    the reference vs the same model in float32 at highest precision."""
    import jax
    import jax.numpy as jnp

    from repro.models import Model

    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, 1024), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks, "lengths": jnp.asarray([1024], jnp.int32)}

    def logits(model, p):
        fn = jax.jit(lambda p, b: model.prefill(p, b)[0])
        return fn.lower(p, batch).as_text(), np.asarray(fn(p, batch))

    k_text, k_out = logits(Model(cfg), params)
    r_text, r_out = logits(Model(cfg, use_kernels=False), params)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        _, f_out = logits(Model(cfg32, use_kernels=False), p32)
    del p32
    n_kernels = k_text.count("tpu_custom_call")
    floor, k_dist = rel_l2(r_out, f_out), rel_l2(k_out, f_out)
    print(f"logits_vs_ref: shape={k_out.shape} kernel_calls_in_program="
          f"{n_kernels} ref_calls_in_program="
          f"{r_text.count('tpu_custom_call')} kernel_vs_ref_rel_l2="
          f"{rel_l2(k_out, r_out):.4e} kernel_vs_f32_rel_l2={k_dist:.4e} "
          f"ref_vs_f32_rel_l2={floor:.4e} tol=kernel_vs_f32<="
          f"{LOGITS_TOL}*ref_vs_f32 argmax_equal="
          f"{int(k_out.argmax()) == int(r_out.argmax())}", flush=True)
    checks.expect(k_out.shape == (1, cfg.vocab_size)
                  and np.isfinite(k_out).all(), "logits shape or finiteness")
    checks.expect(n_kernels > 0, "the kernel prefill program has no kernel")
    checks.expect(k_dist <= LOGITS_TOL * floor,
                  f"logits: kernel path {k_dist:.4e} from the f32 model, "
                  f"more than {LOGITS_TOL}x the reference's {floor:.4e}")


# ------------------------------------------------------------------- serve


def prompts(cfg, seed):
    rng = np.random.default_rng(seed)
    turn1 = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
             for n in TURN1]
    turn2 = {i: np.concatenate([turn1[i], rng.integers(
        0, cfg.vocab_size, (n,)).astype(np.int32)]) for i, n in GROW.items()}
    return turn1, turn2


def serve(cfg, params, seed, paged, checks, clock):
    from repro.core.router import PD, PRFAAS
    from repro.models import Model
    from repro.serving import CrossDCDeployment, DeploymentConfig, Request

    layout = "paged" if paged else "dense"
    c0, t0 = clock.seconds, time.perf_counter()
    dep = CrossDCDeployment(Model(cfg), params, DeploymentConfig(
        threshold=THRESHOLD, pd_clusters=1, wire_compression=True,
        max_prefill_bucket=MAX_BUCKET, capacity=CAPACITY,
        decode_slots=DECODE_SLOTS, pool_blocks=POOL_BLOCKS,
        paged_kv=paged))
    turn1, turn2 = prompts(cfg, seed)
    r1 = [Request(rid=i, tokens=t, max_new_tokens=MAX_NEW)
          for i, t in enumerate(turn1)]
    out = dep.submit_batch(r1)
    r2 = [Request(rid=100 + i, tokens=t, max_new_tokens=MAX_NEW)
          for i, t in turn2.items()]
    out.update(dep.submit_batch(r2))
    wall = time.perf_counter() - t0
    reqs = r1 + r2
    # the prefill's first token, then MAX_NEW decoded ones
    served = [r for r in reqs if r.rid in out
              and len(out[r.rid].output_tokens) == MAX_NEW + 1
              and out[r.rid].finished and not out[r.rid].truncated]
    routes = {PD: sum(r.route == PD for r in r1),
              PRFAAS: sum(r.route == PRFAAS for r in r1)}
    cached = [r.cached_tokens for r in r2]
    link = dep.topology.sent_bytes
    tokens = sum(len(out[r.rid].output_tokens) for r in served)
    prompt_tokens = sum(len(r.tokens) for r in reqs)
    prefilled = dep.prfaas.tokens_prefilled + dep.pd_prefill.tokens_prefilled
    print(f"serve[{layout}]: requests_served={len(served)}/{len(reqs)} "
          f"tokens_served={tokens} turn1_routes={routes} "
          f"turn2_routes={[r.route for r in r2]} "
          f"turn2_cached_tokens={cached} prompt_tokens={prompt_tokens} "
          f"prefilled_tokens={prefilled} link_bytes={link:.0f} "
          f"wire_compression={dep.measured_compression():.3f} "
          f"wall_s={wall:.1f} compile_s={clock.seconds - c0:.1f}",
          flush=True)
    checks.expect(len(served) == len(reqs),
                  f"{layout}: {len(served)}/{len(reqs)} requests returned "
                  f"{MAX_NEW} new tokens")
    checks.expect(routes[PD] > 0 and routes[PRFAAS] > 0,
                  f"{layout}: turn 1 did not take both routes {routes}")
    checks.expect(all(c > 0 for c in cached),
                  f"{layout}: turn 2 cached tokens {cached}")
    checks.expect(link > 0, f"{layout}: no bytes on the link")
    summary = {"requests": f"{len(served)}/{len(reqs)}", "routes": routes,
               "turn2_cached_tokens": cached, "link_bytes": round(link)}
    tokens_by_rid = {r.rid: out[r.rid].output_tokens for r in served}
    return summary, tokens_by_rid


def build_params(cfg, seed):
    """Random parameters from ``seed``.  Eager on purpose: op-by-op init
    compiles one small program per distinct leaf shape, where ``jit`` of
    the whole init compiles one program of every leaf's RNG (~70 s)."""
    import jax

    from repro.models import Model
    return jax.block_until_ready(Model(cfg).init(jax.random.PRNGKey(seed)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    # libtpu otherwise writes its logs under /tmp, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {dev.platform!r} "
                 "(nothing was run)")

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    checks = Checks()
    t_start = time.perf_counter()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())} compile_cache={cache_dir}",
          flush=True)

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = build_params(cfg, args.seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"params={n_params} seed={args.seed} "
          f"build_s={time.perf_counter() - t0:.1f}", flush=True)

    check_kernels(cfg, args.seed, checks)
    check_logits(cfg, params, args.seed, checks)
    gc.collect()

    summaries, streams = {}, {}
    for paged in (False, True):
        layout = "paged" if paged else "dense"
        summaries[layout], streams[layout] = serve(cfg, params, args.seed,
                                                   paged, checks, clock)
        gc.collect()
    same = sum(streams["dense"].get(rid) == toks
               for rid, toks in streams["paged"].items())
    print(f"dense_vs_paged: identical_token_streams={same}/"
          f"{len(streams['paged'])} (informational: greedy ties may split "
          "between the two decode kernels)", flush=True)

    stats = dev.memory_stats() or {}
    print(f"compile: seconds={clock.seconds:.1f} "
          f"backend_compiles={clock.compiles}", flush=True)
    print(f"memory: peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    print(f"wall: seconds={time.perf_counter() - t_start:.1f}", flush=True)
    if checks.failed:
        print(f"chip_smoke: {len(checks.failed)} check(s) failed: "
              f"{checks.failed}", file=sys.stderr, flush=True)
        sys.exit(1)
    print("serve_summary: " + json.dumps(summaries), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
