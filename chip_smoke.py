#!/usr/bin/env python3
"""Bring-up check on a TPU: the serving main path at published widths.

Phase 1 serves mistral-nemo-12b (d_model 5120, 32/8 heads, head_dim 128,
d_ff 14336, vocab 131072) cut to 12 of its 40 layers, so that its bf16
weights take about 9.2 GB of one 16 GB v5e, through the normal entry point
`repro.launch.serve.main` (plan_serving -> Engine -> PagedJaxExecutor with
--kv paged --compact --chunk-prefill). Every request must complete, and the
compiled Pallas kernels must be what decode, chunked prefill and
whole-prompt prefill ran.

Phase 2 runs the compiled kernels against the jnp paths of the same
serving code (models.attention) on the same inputs at the real head shape,
for bf16 and int8 pools, each with the tolerance stated beside its check.

    python3 chip_smoke.py            # one chip: phases 1 and 2
    python3 chip_smoke.py --chips 4  # the sharded path on a model:4 mesh

With --chips 4 the script serves all 40 layers over a `model:4` mesh, then
serves the 12-layer cut once on device 0 alone and once on `model:4`, and
compares greedy tokens and first-token logits of the two; a 4-layer f32
cut at highest matmul precision repeats the comparison with the rounding
noise of bf16 taken out.

One process, no children. Exits nonzero, printing no result, when JAX finds
no TPU or the device kind is not in repro.hw.DEVICES, or when any phase
fails. The last line of a passing run is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "mistral-nemo-12b"
DEPTH = 12           # of 40 layers: 12 x 272.6M + 2 x 671M params, bf16


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Counts XLA compiles and their seconds through jax.monitoring."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.event = dispatch.BACKEND_COMPILE_EVENT
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.event:
            self.count += 1
            self.seconds += duration

    def mark(self):
        return self.count, self.seconds


def run_phase(name, fn, clock, results):
    """Run one phase; a phase passes only if it returns without raising
    and its checks hold. Prints compile and wall seconds."""
    c0, s0 = clock.mark()
    t0 = time.perf_counter()
    try:
        ok = bool(fn())
    except Exception:
        traceback.print_exc()
        ok = False
    wall = time.perf_counter() - t0
    c1, s1 = clock.mark()
    log(f"[{name}] {'PASS' if ok else 'FAIL'} wall={wall:.1f}s "
        f"compiles={c1 - c0} compile_s={s1 - s0:.1f}")
    results.append((name, ok))
    return ok


def device_line(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
            f"bytes_in_use={stats.get('bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")


def check_impls(ran, paths):
    """The attention `paths` traced (kernels.ops.record_traces) the
    compiled kernels and nothing else."""
    log(f"  attention implementations traced: {dict(ran)}")
    ok = True
    for path in paths:
        if not ran.get((path, "compiled")):
            log(f"  FAIL: {path} never traced the compiled Pallas kernel")
            ok = False
        for impl in ("jnp", "interpret"):
            if ran.get((path, impl)):
                log(f"  FAIL: {path} traced the {impl} implementation")
                ok = False
    return ok


# ---------------------------------------------------------------------------
# Phase 1: serve at published widths
# ---------------------------------------------------------------------------

SERVE_ARGS = ["--arch", ARCH, "--depth", str(DEPTH), "--kv", "paged",
              "--compact", "--chunk-prefill", "256", "--kv-block", "128",
              "--requests", "4", "--prompt-lens", "192,384",
              "--gen-lens", "24,40", "--arrival-mean", "0",
              "--max-slots", "4", "--seed", "0"]


def serve_phase(argv, devices, n_requests):
    from repro.kernels import ops as kops
    from repro.launch import serve
    out = {}
    log(f"  serve argv: {' '.join(argv)}")
    with kops.record_traces() as ran:
        rc = serve.main(argv, out=out)
    ok = rc == 0
    reports = out.get("reports", [])
    for rep in reports:
        done = len(rep.completions)
        toks = [t for c in rep.completions for t in c.tokens]
        log(f"  completed {done}/{n_requests} requests, "
            f"{len(toks)} tokens generated")
        if done != n_requests or not toks:
            ok = False
    ok &= check_impls(ran, ("prompt_prefill", "chunk_prefill", "decode"))
    for d in devices:
        log(f"  device {d.id}: {device_line(d)} vs planner budget="
            f"{out.get('budget_bytes', 0):.0f} B, Eq.11 requirement <= "
            f"{out.get('promised_bytes', 0):.0f} B (printed, not judged)")
    return ok


# ---------------------------------------------------------------------------
# Phase 2: compiled kernels vs the jnp paths, real head shape
# ---------------------------------------------------------------------------

LAYER = 1                       # the layer of the stacked pool the checks use


def _paged_case(quant, seed=0, b=4, bs=16, m_blocks=8, n_blocks=64,
                K=8, G=4, hd=128, C=32):
    """A two-layer stacked pool whose layer `LAYER` holds per-lane history
    written by the jnp writer, block tables over disjoint physical blocks
    (0 is scratch), decode cursors, and a prompt chunk per lane (the last
    lane's chunk is short)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import attention as A
    rng = np.random.default_rng(seed)
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    bf = jnp.bfloat16
    starts = [37, 0, 64, 5]                  # history length per lane
    n_valid = [C, C, C, C // 2 + 3]
    n = 2
    cache = {"pos": jnp.full((n, n_blocks, bs), -1, jnp.int32)}
    pool_dt = {"none": bf, "int8": jnp.int8}[quant]
    cache["kb"] = jnp.zeros((n, n_blocks, bs, K, hd), pool_dt)
    cache["vb"] = jnp.zeros((n, n_blocks, bs, K, hd), pool_dt)
    if quant != "none":
        cache["ks"] = jnp.zeros((n, n_blocks, bs, K), jnp.float32)
        cache["vs"] = jnp.zeros((n, n_blocks, bs, K), jnp.float32)
    perm = rng.permutation(np.arange(1, n_blocks)).tolist()
    tables = np.full((b, m_blocks), -1, np.int32)
    for i in range(b):
        for j in range(-(-(starts[i] + n_valid[i]) // bs)):
            tables[i, j] = perm.pop()
    tables = jnp.asarray(tables)
    H = max(starts)
    hist = jnp.asarray([[p if p < st else -1 for p in range(H)]
                        for st in starts], jnp.int32)
    cache = A._paged_write_chunk(
        cache, LAYER, tables, jax.random.normal(ks[0], (b, H, K, hd), bf),
        jax.random.normal(ks[1], (b, H, K, hd), bf), hist)
    chunk_pos = jnp.asarray([[st + c if c < nv else -1 for c in range(C)]
                             for st, nv in zip(starts, n_valid)], jnp.int32)
    return dict(
        cache=cache, tables=tables, chunk_pos=chunk_pos,
        q_chunk=jax.random.normal(ks[2], (b, C, K, G, hd), bf),
        k_chunk=jax.random.normal(ks[3], (b, C, K, hd), bf),
        v_chunk=jax.random.normal(ks[4], (b, C, K, hd), bf),
        q1=jax.random.normal(ks[5], (b, 1, K, G, hd), bf),
        k1=jax.random.normal(ks[6], (b, K, hd), bf),
        v1=jax.random.normal(ks[7], (b, K, hd), bf),
        pos1=jnp.asarray([st - 1 for st in starts], jnp.int32)
        .at[1].set(0))


def _max_err(a, b, rows=None):
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if rows is not None:
        a, b = a[rows], b[rows]
    return float(np.max(np.abs(a - b)))


def kernel_phase():
    """Every check of _kernel_checks holds, and the kernel side of each
    comparison ran compiled (never interpreted)."""
    from repro.kernels import ops as kops
    with kops.record_traces() as ran:
        ok = _kernel_checks()
    log(f"  attention implementations traced: {dict(ran)}")
    for path in ("decode", "chunk_prefill", "prompt_prefill"):
        if not ran.get((path, "compiled")) or ran.get((path, "interpret")):
            log(f"  FAIL: {path} did not run the compiled kernel")
            ok = False
    return ok


def _kernel_checks():
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.base import BlockSpec
    from repro.models import attention as A

    kernel = A.AttnSettings(backend="pallas")
    jnp_path = A.AttnSettings(backend="blocked")
    blk = BlockSpec()
    ok = True

    # Output tolerance: both paths read the same bf16 (or int8-coded)
    # KV and emit bf16; |kernel - jnp| <= 3e-2 absolute on outputs of
    # order 1 allows a few bf16 ulps of accumulated rounding.
    OUT_ATOL = 3e-2
    for quant in ("none", "int8"):
        case = _paged_case(quant)
        cache, tables = case["cache"], case["tables"]

        decode = {
            name: jax.jit(functools.partial(
                A._paged_decode, blk=blk, settings=st, layer=LAYER))
            for name, st in (("kernel", kernel), ("jnp", jnp_path))}
        res = {n: f(case["q1"], cache, pos1=case["pos1"], k1=case["k1"],
                    v1=case["v1"], block_tables=tables)
               for n, f in decode.items()}
        err = _max_err(res["kernel"][0], res["jnp"][0])
        good = np.isfinite(np.asarray(res["kernel"][0], np.float32)).all() \
            and err <= OUT_ATOL
        log(f"  decode[{quant}]: max|kernel-jnp|={err:.3e} "
            f"(tolerance {OUT_ATOL}) {'ok' if good else 'FAIL'}")
        ok &= bool(good)

        prefill = {
            name: jax.jit(functools.partial(
                A._chunk_append, blk=blk, settings=st, layer=LAYER))
            for name, st in (("kernel", kernel), ("jnp", jnp_path))}
        res = {n: f(case["q_chunk"], case["k_chunk"], case["v_chunk"],
                    cache, positions=case["chunk_pos"],
                    block_tables=tables)
               for n, f in prefill.items()}
        valid = np.asarray(case["chunk_pos"]) >= 0
        err = _max_err(res["kernel"][0], res["jnp"][0], rows=valid)
        good = err <= OUT_ATOL
        log(f"  chunk_prefill[{quant}]: max|kernel-jnp| over valid rows="
            f"{err:.3e} (tolerance {OUT_ATOL}) {'ok' if good else 'FAIL'}")
        ok &= bool(good)
        # The pools the two paths wrote must agree, every layer, outside
        # scratch block 0: positions exactly; bf16 payload exactly (the
        # kernel's merge is a full-precision one-hot copy); int8 codes
        # within 1 (the chip's f32 division may round a .5 tie the other
        # way) and f32 scales within a relative 1e-6.
        kc, jc = res["kernel"][1], res["jnp"][1]
        pos_ok = np.array_equal(np.asarray(kc["pos"])[:, 1:],
                                np.asarray(jc["pos"])[:, 1:])
        pay = max(_max_err(np.asarray(kc[k])[:, 1:],
                           np.asarray(jc[k])[:, 1:]) for k in ("kb", "vb"))
        pay_tol = 0.0 if quant == "none" else 1.0
        scale_err = 0.0
        if quant != "none":
            for k in ("ks", "vs"):
                a = np.asarray(kc[k])[:, 1:]
                w = np.asarray(jc[k])[:, 1:]
                scale_err = max(scale_err, float(
                    np.max(np.abs(a - w) / np.maximum(np.abs(w), 1e-30))))
        good = pos_ok and pay <= pay_tol and scale_err <= 1e-6
        log(f"  chunk_prefill[{quant}] pool: positions "
            f"{'equal' if pos_ok else 'DIFFER'}, payload max diff={pay} "
            f"(tolerance {pay_tol}), scale rel diff={scale_err:.2e} "
            f"(tolerance 1e-6) {'ok' if good else 'FAIL'}")
        ok &= bool(good)

    # whole-prompt prefill: flash kernel vs the blocked jnp tiling
    b, s, K, G, hd = 2, 384, 8, 4, 128
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, s, K, G, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, K, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, K, hd), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    outs = [jax.jit(functools.partial(A._seq_attention, blk=blk, set_=st))(
        q, k, v, pos, pos) for st in (kernel, jnp_path)]
    err = _max_err(*outs)
    good = err <= OUT_ATOL
    log(f"  prompt_prefill: max|kernel-jnp|={err:.3e} "
        f"(tolerance {OUT_ATOL}) {'ok' if good else 'FAIL'}")
    ok &= bool(good)
    return ok


# ---------------------------------------------------------------------------
# Four chips: the sharded path and its one-device reference
# ---------------------------------------------------------------------------

def serve_on(cfg, devs, trace, *, kv_block=128, chunk=256):
    """Serve `trace` with seed-0 weights on a data:1 x model:len(devs)
    mesh through Engine + PagedJaxExecutor (compiled kernels on a TPU).
    Returns (first-token logits [n_requests, V] from the plain prefill
    step, {rid: greedy tokens})."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.mesh import build_mesh
    from repro.models import init_params
    from repro.parallel import sharding as SH
    from repro.parallel.axes import axis_rules
    from repro.runtime import serve_step as SS
    from repro.serving import BlockAllocator, Engine, trace_context
    from repro.serving.executor import PagedJaxExecutor

    context = -(-trace_context(trace) // kv_block) * kv_block
    lanes = len(trace)
    n_blocks = lanes * (context // kv_block)
    mesh = build_mesh({"data": 1, "model": len(devs)}, devs)
    strategy = SH.default_strategy(cfg, mesh)
    with mesh, axis_rules(strategy.rules(), mesh=mesh):
        key = jax.random.PRNGKey(0)
        abstract = jax.eval_shape(lambda k: init_params(k, cfg), key)
        params = init_params(key, cfg, SH.to_named(
            mesh, SH.param_specs(cfg, abstract, strategy, mesh)))
        prefill = SS.serve_steps(cfg)[0]
        logits = np.stack([np.asarray(prefill(
            params, jnp.asarray([r.prompt], jnp.int32),
            context=context)[0], np.float32)[0] for r in trace])
        ex = PagedJaxExecutor(params, cfg, n_lanes=lanes, n_blocks=n_blocks,
                              kv_block=kv_block, context=context,
                              compact=True, chunk=chunk)
        rep = Engine(ex, lanes, allocator=BlockAllocator(n_blocks, kv_block),
                     chunk_prefill=chunk).run(trace)
        toks = {c.rid: list(c.tokens) for c in rep.completions}
        del params, ex, rep
    log(f"  mesh={dict(mesh.shape)} param_dtype={cfg.param_dtype} "
        f"layers={cfg.n_layers}: completed {len(toks)}/{len(trace)}")
    return logits, toks


def compare_meshes(cfg, devices, *, logit_rtol, token_agreement,
                   first_token_margin=2.0, prompt_lens=(192, 384),
                   gen_lens=(24, 40), kv_block=128, chunk=256):
    """Serve one trace with the same weights on devices[0] alone and on a
    model:4 mesh over devices[:4], in this process, and compare.

    - first-token logits of every prompt: max |model4 - device0| over
      max |device0| <= `logit_rtol`;
    - first greedy token of every prompt: equal wherever device0's top-2
      logit margin exceeds `first_token_margin` x the max logit difference
      (a closer call may flip under the reduction order of tensor
      parallelism);
    - greedy tokens of the whole run: the fraction of positions equal, up
      to each request's first divergence, >= `token_agreement` (one flip
      changes the rest of a sequence, so later positions are not
      compared)."""
    import numpy as np
    from repro.serving import synthetic_trace
    trace = synthetic_trace(4, vocab_size=cfg.vocab_size, seed=0,
                            prompt_lens=prompt_lens, gen_lens=gen_lens,
                            mean_interarrival=0)
    ref_l, ref_t = serve_on(cfg, devices[:1], trace, kv_block=kv_block,
                            chunk=chunk)
    l4, t4 = serve_on(cfg, devices[:4], trace, kv_block=kv_block,
                      chunk=chunk)
    diff = np.abs(l4 - ref_l)
    rel = float(diff.max() / np.abs(ref_l).max())
    top2 = np.sort(ref_l, axis=1)[:, -2:]
    margin = top2[:, 1] - top2[:, 0]
    decisive = margin > first_token_margin * float(diff.max())
    first_ok = bool(np.all((l4.argmax(1) == ref_l.argmax(1))[decisive]))
    same = total = 0
    prefixes = []
    for rid, seq in ref_t.items():
        other = t4.get(rid, [])
        n = 0
        while n < min(len(seq), len(other)) and seq[n] == other[n]:
            n += 1
        prefixes.append(f"{n}/{len(seq)}")
        same += n
        total += len(seq)
    agree = same / max(total, 1)
    ok = (len(ref_t) == len(t4) == len(trace) and rel <= logit_rtol
          and first_ok and agree >= token_agreement)
    log(f"  first-token logits: max|model4-device0|/max|device0|={rel:.3e} "
        f"(tolerance {logit_rtol}); argmax equal on "
        f"{int((l4.argmax(1) == ref_l.argmax(1)).sum())}/{len(trace)} "
        f"prompts, {int(decisive.sum())} decisive (margin > "
        f"{first_token_margin} x max diff) "
        f"{'all equal' if first_ok else 'NOT all equal'}; greedy "
        f"common prefixes {prefixes} = {agree:.3f} (tolerance >= "
        f"{token_agreement}) {'ok' if ok else 'FAIL'}")
    return ok


def sharded_phases(devices, clock, results):
    """Four chips: all 40 layers on model:4 through the serve entry point,
    then the 12-layer cut on one device vs model:4 in bf16 (where the
    bf16 rounding of tensor-parallel partial sums is the expected
    difference), and a 4-layer cut in f32 at highest matmul precision
    (where any difference beyond rounding would be a sharding fault)."""
    import dataclasses

    import jax
    from repro.configs import get_config
    from repro.configs.base import depth_variant
    full = ["--arch", ARCH, "--kv", "paged", "--compact",
            "--chunk-prefill", "256", "--kv-block", "128",
            "--requests", "4", "--prompt-lens", "192,384",
            "--gen-lens", "24,40", "--arrival-mean", "0",
            "--max-slots", "4", "--seed", "0", "--model-parallel", "4"]
    run_phase("serve_40_layers_model4",
              lambda: serve_phase(full, devices, 4), clock, results)
    cfg = depth_variant(get_config(ARCH), DEPTH)
    run_phase("depth_cut_bf16_device0_vs_model4",
              lambda: compare_meshes(cfg, devices, logit_rtol=5e-2,
                                     token_agreement=0.0),
              clock, results)

    def exact():
        f32 = dataclasses.replace(depth_variant(get_config(ARCH), 4),
                                  param_dtype="float32")
        with jax.default_matmul_precision("highest"):
            return compare_meshes(f32, devices, logit_rtol=1e-3,
                                  token_agreement=0.9)
    run_phase("depth4_f32_device0_vs_model4", exact, clock, results)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve + kernel parity on one chip; 4: only "
                         "the model:4 sharded path and its comparison")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: no repro package next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro import hw
    spec = hw.for_device_kind(dev.device_kind)     # unknown kind raises
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache
    log(f"device: kind={dev.device_kind!r} count={len(devices)} "
        f"table entry={spec.name} compile cache={setup_compile_cache()}")
    clock = CompileClock()
    results = []
    if args.chips == 4:
        sharded_phases(devices[:4], clock, results)
    else:
        run_phase("serve", lambda: serve_phase(SERVE_ARGS, [dev], 4),
                  clock, results)
        run_phase("kernel_parity", kernel_phase, clock, results)
    log(f"total: compiles={clock.count} compile_s={clock.seconds:.1f}")
    failed = [n for n, ok in results if not ok]
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
