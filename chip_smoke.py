"""Bring-up check on TPU: the planner, the trainer, the server and the
Pallas kernels, driven through the repository's own entry points in one
process.

  python chip_smoke.py                # one chip: phases 0-5
  python chip_smoke.py --four-chips   # four chips: phase 0, then the
                                      # sharded planner ladder, per-chip
                                      # peaks and a 4-chip vs 1-chip check

  0 device    the first device must be a TPU: there is no CPU fallback
  1 decode    deepseek-7b at published widths, cut to the planner's
              deepest ladder depth, decodes a cut of decode_32k on real
              parameters and caches; measured HBM beside compiled bytes.
              First, because the peak is a process-wide high-water mark
  2 allocate  one request through AllocationPipeline; the planner's
              profile_at compiles each ladder point for this chip
  3 train     launch/train.py on whisper-small, published widths and depth
  4 serve     launch/serve.py on whisper-small at published widths
  5 kernels   each Pallas kernel at the widths of a config that uses it,
              compiled for the chip and compared with kernels/ref.py

Prints one line per phase with its numbers. The last line is the JSON
verdict, printed only when every phase passed; any failure exits non-zero.
Weights and data are random, made from fixed seeds.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
GiB = 1024 ** 3
# tests/test_kernels.py's tolerance for bfloat16 inputs
BF16_TOL = dict(atol=2e-2, rtol=2e-2)


def log(msg: str) -> None:
    print(msg, flush=True)


def gib(n: float) -> str:
    return f"{n / GiB:.3f}GiB"


def mem_stats(dev) -> dict:
    stats = dev.memory_stats()
    if stats is None:
        raise RuntimeError(f"{dev} reports no memory stats")
    return stats


def decode_cut():
    """decode_32k cut to fit one chip: at seq 32768 x batch 128 the bf16
    KV cache of a single deepseek-7b layer is 64 GiB. At batch 8 the
    7-layer step compiles to 15.04 GiB of the v5e's 15.75 GiB; batch 4
    leaves room for the process's other buffers."""
    from repro.configs import SHAPES
    return dataclasses.replace(SHAPES["decode_32k"], seq_len=2048,
                               global_batch=4)


def one_chip_mesh(dev):
    from repro.launch.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"), devices=[dev])


# -- phase 0 ----------------------------------------------------------------
def device_phase(n_chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (jax sees {len(devs)} "
                 f"{d.platform} device(s)); there is no CPU fallback")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: {n_chips} chips needed, {len(devs)} found")
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.roofline import chip_peaks
    peaks = chip_peaks(d.device_kind)
    cache = use_compile_cache()
    log(f"[0 device] kind={d.device_kind!r} count={len(devs)} "
        f"compile_cache={cache} peaks: {peaks.flops / 1e12:g} TFLOP/s, "
        f"{peaks.hbm_bytes / 1e9:g} GB HBM at {peaks.hbm_bw / 1e9:g} GB/s "
        f"({peaks.source})")
    return devs


# -- phase 1 ----------------------------------------------------------------
def run_decode(cfg, shape, mesh, steps: int, run=None, params=None,
               tokens=None):
    """Compile `Model.decode_step` for `mesh`, build parameters and caches
    on it and decode `steps` tokens. Feeds `tokens[i]` at step i when
    given, else the greedy token. Returns (compiled, params, [logits])."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.dryrun import build_lowered

    lowered, model = build_lowered(cfg, shape, mesh, run)
    compiled = lowered.compile()
    p_sh, b_sh, c_sh = compiled.input_shardings[0]
    B, S = shape.global_batch, shape.seq_len
    if params is None:
        params = jax.jit(model.init, out_shardings=p_sh)(
            jax.random.PRNGKey(0))
    else:
        params = jax.device_put(params, p_sh)
    caches = jax.jit(lambda: model.init_caches(B, S), out_shardings=c_sh)()
    greedy = jax.jit(
        lambda lg: jnp.argmax(lg[:, -1, :cfg.vocab_size], -1)[:, None]
        .astype(jnp.int32), out_shardings=b_sh["tokens"])
    tok = jax.device_put(np.ones((B, 1), np.int32), b_sh["tokens"])
    out = []
    for i in range(steps):
        if tokens is not None:
            tok = jax.device_put(tokens[i], b_sh["tokens"])
        logits, caches = compiled(params, {"tokens": tok}, caches)
        out.append(logits)
        tok = greedy(logits)
    jax.block_until_ready((out, caches))
    return compiled, params, out


def decode_phase(dev, cfg, shape, full_depth: int) -> None:
    import numpy as np
    from repro.core.hbm_planner import compiled_bytes

    steps = 4
    before = mem_stats(dev)["bytes_in_use"]
    t0 = time.monotonic()
    compiled, _, logits = run_decode(cfg, shape, one_chip_mesh(dev), steps)
    wall = time.monotonic() - t0
    peak = mem_stats(dev)["peak_bytes_in_use"]
    want = compiled_bytes(compiled)
    xla_peak = compiled.memory_analysis().peak_memory_in_bytes
    for lg in logits:
        lg = np.asarray(lg[:, :, :cfg.vocab_size], np.float32)
        if lg.shape != (shape.global_batch, 1, cfg.vocab_size):
            raise RuntimeError(f"decode logits shape {lg.shape}")
        if not np.isfinite(lg).all():
            raise RuntimeError("decode logits are not finite")
    log(f"[1 decode] {cfg.name} d_model={cfg.d_model} vocab={cfg.vocab_size} "
        f"d_ff={cfg.d_ff} heads={cfg.n_heads} layers={cfg.n_layers} of "
        f"{full_depth}; decode_32k cut to seq {shape.seq_len} x batch "
        f"{shape.global_batch}; {steps} steps in {wall:.1f}s incl. compile; "
        f"bytes_in_use before={before} peak_bytes_in_use after={peak} "
        f"({gib(peak)}) compiled={int(want)} ({gib(want)}) "
        f"peak/compiled={peak / want:.3f} xla_peak_memory={xla_peak} "
        f"({gib(xla_peak)})")


# -- phase 2 ----------------------------------------------------------------
def allocate_phase(dev, cfg, shape) -> None:
    from repro.core.catalog import tpu_catalog
    from repro.core.hbm_planner import HBMPlanner, TPU_OVERHEAD_GIB
    from repro.core.history import ExecutionHistory
    from repro.pipeline import AllocationPipeline, PipelineRequest

    planner = HBMPlanner()
    # the planner's integer depth ladder; its anchor is the deepest point
    ladder = planner.ladder(cfg)
    tr = AllocationPipeline(
        tpu_catalog(), ExecutionHistory(),
        overhead_per_node_gib=TPU_OVERHEAD_GIB).run(PipelineRequest(
            f"{cfg.name}:{shape.name}:seq{shape.seq_len}xb"
            f"{shape.global_batch}",
            planner.profile_at(cfg, shape, one_chip_mesh(dev)),
            full_size=cfg.n_layers, sizes=ladder))
    config = tr.selection.config
    log(f"[2 allocate] job={tr.job} full_size={cfg.n_layers} "
        f"anchor={ladder[-1]} ladder={ladder} "
        f"per_dev={[gib(r.peak_mem_bytes) for r in tr.results]} "
        f"compile_s={[round(r.wall_s, 1) for r in tr.results]}; "
        f"requirement_gib={tr.requirement_gib:.2f} "
        f"config={config.name} ({config.scale_out} chips) "
        f"source={tr.source} profiled={tr.plan.profiled} "
        f"wall_s={tr.wall_s:.1f}")
    if tr.plan.profiled < 2:
        raise RuntimeError(f"only {tr.plan.profiled} ladder points profiled")
    if tr.source != "zoo":
        raise RuntimeError(f"no confident memory model ({tr.source})")
    if config.scale_out <= 1:
        raise RuntimeError(f"{cfg.n_layers} layers cannot fit one chip, "
                           f"yet {config.name} was selected")


# -- phases 3 and 4 ---------------------------------------------------------
def train_phase(argv) -> None:
    import numpy as np
    from repro.launch.train import main as train_main

    t0 = time.monotonic()
    report = train_main(argv)
    wall = time.monotonic() - t0
    if not report.losses or not np.isfinite(report.losses).all():
        raise RuntimeError(f"train losses {report.losses}")
    log(f"[3 train] {' '.join(argv)}: {report.final_step} steps, losses "
        f"{[round(x, 4) for x in report.losses]}, step_s "
        f"{[round(t, 3) for t in report.step_times]}, wall {wall:.1f}s")


def serve_phase(argv, n_requests: int, max_new: int, vocab: int) -> None:
    from repro.launch.serve import main as serve_main

    t0 = time.monotonic()
    done = serve_main(argv)
    wall = time.monotonic() - t0
    toks = [t for r in done for t in r.out_tokens]
    if len(done) != n_requests or any(len(r.out_tokens) != max_new
                                      for r in done):
        raise RuntimeError(f"served {[len(r.out_tokens) for r in done]}")
    if not all(0 <= t < vocab for t in toks):
        raise RuntimeError("served a token outside the vocabulary")
    log(f"[4 serve] {' '.join(argv)}: {len(done)} requests, {len(toks)} "
        f"tokens, wall {wall:.1f}s incl. compile")


# -- phase 5 ----------------------------------------------------------------
def kernel_cases():
    """(name, kernel, reference, argument maker) at real widths."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch
    from repro.kernels import ops, ref

    ds, wh = get_arch("deepseek-7b"), get_arch("whisper-small")
    zb, rw = get_arch("zamba2-7b"), get_arch("rwkv6-7b")
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(i, shape, dtype=bf16):
        return jax.random.normal(jax.random.PRNGKey(i), shape, dtype)

    def attn(B, S, H, D):
        return lambda: tuple(normal(i, (B, S, H, D)) for i in range(3))

    H_ssd = zb.d_model * zb.ssm.expand // zb.ssm.head_dim
    P, N = zb.ssm.head_dim, zb.ssm.d_state

    def ssd_args(B=1, S=1024):
        return (normal(0, (B, S, H_ssd, P)),
                jax.nn.softplus(normal(1, (B, S, H_ssd), f32)),
                -jnp.exp(normal(2, (H_ssd,), f32)),
                normal(3, (B, S, H_ssd, N)), normal(4, (B, S, H_ssd, N)))

    H_w, K = rw.n_heads, rw.d_model // rw.n_heads

    def wkv_args(B=1, S=512):
        return (normal(0, (B, S, H_w, K)), normal(1, (B, S, H_w, K)),
                normal(2, (B, S, H_w, K)),
                -jnp.exp(normal(3, (B, S, H_w, K), f32)),
                0.3 * normal(4, (H_w, K), f32))

    return [
        (f"flash_attention d_head={ds.d_head} (deepseek-7b, causal)",
         ops.flash_attention, ref.attention_ref,
         attn(1, 2048, ds.n_heads, ds.d_head)),
        (f"flash_attention d_head={wh.d_head} (whisper-small encoder)",
         lambda q, k, v: ops.flash_attention(q, k, v, causal=False),
         lambda q, k, v: ref.attention_ref(q, k, v, causal=False),
         attn(2, wh.encdec.enc_len, wh.n_heads, wh.d_head)),
        (f"rmsnorm d={ds.d_model} (deepseek-7b)", ops.rmsnorm,
         ref.rmsnorm_ref,
         lambda: (normal(0, (4, 2048, ds.d_model)),
                  1.0 + 0.1 * normal(1, (ds.d_model,), f32))),
        (f"ssd H={H_ssd} P={P} N={N} chunk={zb.ssm.chunk} (zamba2-7b)",
         lambda *a: ops.ssd(*a, chunk=zb.ssm.chunk)[0], ref.ssd_ref,
         ssd_args),
        (f"wkv6 H={H_w} K={K} (rwkv6-7b)",
         lambda *a: ops.wkv6(*a)[0], ref.wkv6_ref, wkv_args),
    ]


def kernel_phase(cases) -> None:
    import jax
    import numpy as np

    for name, kernel, reference, make_args in cases:
        args = make_args()
        t0 = time.monotonic()
        compiled = jax.jit(kernel).lower(*args).compile()
        compile_s = time.monotonic() - t0
        # Mosaic code, not interpreted jnp
        if "tpu_custom_call" not in compiled.as_text():
            raise RuntimeError(f"{name}: no tpu_custom_call in the compiled "
                               f"program; the kernel was not compiled for "
                               f"TPU")
        t0 = time.monotonic()
        got = jax.block_until_ready(compiled(*args))
        run_s = time.monotonic() - t0
        with jax.default_matmul_precision("highest"):
            want = jax.jit(reference)(*args)
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want)))
        log(f"[5 kernels] {name}: shapes {[tuple(a.shape) for a in args]} "
            f"compile_s={compile_s:.2f} first_run_s={run_s:.4f} "
            f"max_abs_err={err:.3g} max_abs_ref={np.max(np.abs(want)):.3g} "
            f"tpu_custom_call=yes")
        np.testing.assert_allclose(got, want, **BF16_TOL, err_msg=name)


# -- four chips -------------------------------------------------------------
def four_chip_phase(devs, cfg, train_shape, dshape) -> None:
    """The planner's ladder for training on a (1, 4) data x model mesh,
    measured per-chip peaks at its deepest point, and depth-2 decode
    logits on four chips against one chip."""
    import jax
    import numpy as np
    from repro.core.catalog import tpu_catalog
    from repro.core.hbm_planner import (HBMPlanner, TPU_OVERHEAD_GIB,
                                        _reduced_depth, compiled_bytes)
    from repro.core.history import ExecutionHistory
    from repro.launch.dryrun import build_lowered
    from repro.launch.mesh import make_mesh, mesh_config
    from repro.launch.presets import preset_run
    from repro.optim import AdamWConfig
    from repro.pipeline import AllocationPipeline, PipelineRequest
    from repro.train.step import init_train_state

    devs = devs[:4]
    mesh = make_mesh((1, 4), ("data", "model"), devices=devs)
    planner = HBMPlanner()
    ladder = planner.ladder(cfg)
    deepest = _reduced_depth(cfg, ladder[-1])
    limit = min(mem_stats(d)["bytes_limit"] for d in devs)

    # cut the train batch until the deepest ladder point fits a chip. The
    # presets split a step into one-sequence microbatches, so what sets
    # the memory is the sequence, not the global batch
    t0 = time.monotonic()
    while True:
        per_dev = planner.profile_memory(deepest, train_shape, mesh)
        log(f"[4x ladder] {deepest.name} {deepest.n_layers} layers, "
            f"{train_shape.name} seq {train_shape.seq_len} x batch "
            f"{train_shape.global_batch}: {gib(per_dev)}/chip of "
            f"{gib(limit)}")
        if per_dev <= limit:
            break
        if train_shape.seq_len <= 128:
            raise RuntimeError("the deepest point fits no batch")
        train_shape = dataclasses.replace(
            train_shape, seq_len=train_shape.seq_len // 2)
    tr = AllocationPipeline(
        tpu_catalog(), ExecutionHistory(),
        overhead_per_node_gib=TPU_OVERHEAD_GIB, leeway=0.05).run(
            PipelineRequest(f"{cfg.name}:{train_shape.name}",
                            planner.profile_at(cfg, train_shape, mesh),
                            full_size=cfg.n_layers, sizes=ladder))
    fit = tr.plan.fit
    log(f"[4x ladder] mesh (1, 4) data x model; ladder={ladder} "
        f"per_dev={[gib(b / len(devs)) for b in tr.mems]} "
        f"R2={fit.r2:.5f} predicted at {cfg.n_layers} layers "
        f"{tr.requirement_gib / len(devs):.2f}GiB/chip, requirement "
        f"{tr.requirement_gib:.1f}GiB -> {tr.selection.config.name}; "
        f"wall {time.monotonic() - t0:.1f}s")
    if not fit.confident:
        raise RuntimeError(f"ladder fit not confident: R2={fit.r2}")

    # measured peaks: one train step of the deepest point on the mesh
    lowered, model = build_lowered(deepest, train_shape, mesh)
    compiled = lowered.compile()
    state_sh, b_sh = compiled.input_shardings[0]
    run = model.run
    acfg = AdamWConfig(moment_dtype=run.moment_dtype,
                       keep_master=(run.param_dtype != "float32"))
    state = jax.jit(lambda k: init_train_state(model, k, acfg),
                    out_shardings=state_sh)(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = train_shape.global_batch, train_shape.seq_len
    toks = rng.integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    batch = jax.device_put({"tokens": toks, "labels": toks}, b_sh)
    state, metrics = compiled(state, batch)
    loss = float(metrics["loss"])
    del state
    peaks = [mem_stats(d)["peak_bytes_in_use"] for d in devs]
    want = compiled_bytes(compiled)
    xla_peak = compiled.memory_analysis().peak_memory_in_bytes
    spread = (max(peaks) - min(peaks)) / max(peaks)
    log(f"[4x peaks] {deepest.n_layers}-layer train step, seq {S} x batch "
        f"{B}: loss={loss} peak_bytes_in_use per chip={peaks} "
        f"({[gib(p) for p in peaks]}) compiled per chip={int(want)} "
        f"({gib(want)}) xla_peak_memory={xla_peak} ({gib(xla_peak)}) "
        f"spread={spread:.3f}")
    if not np.isfinite(loss):
        raise RuntimeError(f"train loss {loss}")
    if spread > 0.10:
        raise RuntimeError(f"per-chip peaks differ by {spread:.1%}")

    # the same depth-2 decode on four chips and on one, in full f32
    shallow = _reduced_depth(cfg, 2)
    drun = preset_run(shallow, dshape, mesh_config(mesh)).with_(
        compute_dtype="float32")
    toks = rng.integers(0, cfg.vocab_size, (2, dshape.global_batch, 1),
                        dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        _, params, l4 = run_decode(shallow, dshape, mesh, 2, drun,
                                   tokens=toks)
        _, _, l1 = run_decode(shallow, dshape, one_chip_mesh(devs[0]), 2,
                              drun, params=params, tokens=toks)
    errs = []
    for a, b in zip(l4, l1):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errs.append(float(np.max(np.abs(a - b))))
        np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)
    log(f"[4x sharding] depth-2 decode, seq {dshape.seq_len} x batch "
        f"{dshape.global_batch}, f32: 4-chip vs 1-chip logits max_abs_diff "
        f"per step={errs}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip planner/sharding path")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: {ROOT}/src/repro not found; run from a "
                 f"checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))

    t_start = time.monotonic()
    devs = device_phase(4 if args.four_chips else 1)

    from repro.configs import SHAPES, get_arch
    from repro.core.hbm_planner import HBMPlanner, _reduced_depth

    ds = get_arch("deepseek-7b")
    walls = {}

    def timed(name, fn, *a):
        t0 = time.monotonic()
        fn(*a)
        walls[name] = round(time.monotonic() - t0, 1)

    if args.four_chips:
        # the global batch sets the step time only (see four_chip_phase)
        train = dataclasses.replace(SHAPES["train_4k"], global_batch=16)
        timed("four_chips", four_chip_phase, devs, ds, train, decode_cut())
    else:
        dev = devs[0]
        deepest = _reduced_depth(ds, HBMPlanner.ladder(ds)[-1])
        timed("decode", decode_phase, dev, deepest, decode_cut(),
              ds.n_layers)
        timed("allocate", allocate_phase, dev, ds, decode_cut())
        wh = get_arch("whisper-small")
        timed("train", train_phase,
              ["--arch", wh.name, "--steps", "4", "--batch", "8",
               "--microbatches", "8", "--seq", "448"])
        timed("serve", serve_phase,
              ["--arch", wh.name, "--requests", "8", "--slots", "4",
               "--max-new", "8", "--max-len", "64"], 8, 8, wh.vocab_size)
        timed("kernels", kernel_phase, kernel_cases())
    log(f"[walls] {walls} total {time.monotonic() - t_start:.1f}s")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
