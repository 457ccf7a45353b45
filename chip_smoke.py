#!/usr/bin/env python3
"""Run the serve and train paths once on one TPU chip at qwen3-32b widths.

    python3 chip_smoke.py              # one chip: serve, then train
    python3 chip_smoke.py --chips 4    # four chips: tensor-parallel train

Everything goes through the entry points a user calls, the library
``Engine`` and ``Trainer``, with the compilation cache that
``repro.launch.serve`` and ``repro.launch.train`` use.  Weights are random
from ``--seed``; every width is qwen3-32b's published one
(``repro/configs/qwen3_32b.py``), and each cut to depth, batch or
vocabulary is printed.

* serve: 4 layers, the full 151,936-token vocabulary, bf16 weights
  (3.5 B parameters, 7.0 GB).  A paged KV pool of 8 slots x 4,096 tokens
  (16 KiB per token, 512 MiB) answers 8 seeded requests with prompts of
  256-2,048 tokens and 32 new tokens each.  One request's prefill and
  decode logits are then replayed through the same paged path and
  compared with a plain float32 forward (``repro.models.reference``).
* train: 1 layer, the vocabulary cut to 1/8, f32 AdamW (0.68 B
  parameters, 10.9 GB with gradients); 3 steps at B = 1, T = 4,096 and
  a constant learning rate of 3e-5.  The loss must be finite and fall.
* ``--chips 4``: only the train configuration, once under the plan
  ``model=4`` (tensor parallel over 4 chips) and once unsharded on one
  chip; their step-1 losses must agree.

Each phase prints its wall time, compile time, the device's peak memory
so far, and whether its compiled steps hold a Pallas kernel
(``tpu_custom_call``).  The last line is the JSON verdict.  The script
exits non-zero, before any result, when JAX finds no TPU or when it does
not sit in a checkout of this repository.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# Serve logits vs the float32 reference, as a share of the reference's RMS.
# The served path keeps activations and the residual stream in bf16 (unit
# roundoff 2^-9); ~10 roundings per layer over 4 layers, independent,
# give a relative RMS error near sqrt(40) * 2^-9 / sqrt(3) = 0.7%.  The
# bounds allow 3x that.  One fp8 stage (2^-4 roundoff) would exceed both.
LOGIT_REL_RMS_TOL = 0.02
LOGIT_MAX_ABS_TOL = 0.1          # x RMS of the reference logits
# Step-1 loss, tensor-parallel vs one chip: the same bf16 math summed in
# another order (the bound the distributed CPU test holds TP to).
TP_LOSS_TOL = 2e-2
# Adam's first steps move every weight by about the learning rate; at
# width 5120 a 3e-4 step shifts pre-activations by O(1) and the loss
# jumps (10.36 -> 17.47 on a v5e), while 3e-5 lowers it from the start.
TRAIN_LR = 3e-5


def _repro():
    """Put this checkout's ``src`` on the path, or exit."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}; run this script "
              "from a checkout of the repository", file=sys.stderr)
        sys.exit(2)
    sys.path.insert(0, str(src))


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, from its own
    monitoring events."""

    def __init__(self):
        import jax
        self.seconds = 0.0

        def on_event(name, secs, **_):
            if name.startswith("/jax/core/compile/"):
                self.seconds += secs
        jax.monitoring.register_event_duration_secs_listener(on_event)


def _peak_gib() -> float:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", float("nan")) / 2**30


def _has_kernel(jitted, *args, **kwargs) -> bool:
    """Whether the compiled step calls a Pallas TPU kernel."""
    text = jitted.lower(*args, **kwargs).compile().as_text()
    return "tpu_custom_call" in text


def qwen3(layers: int, vocab_divisor: int = 1):
    from repro.configs import get_config
    cfg = get_config("qwen3-32b")
    return dataclasses.replace(cfg, num_layers=layers,
                               vocab_size=cfg.vocab_size // vocab_divisor)


# ---------------------------------------------------------------------------
def serve_phase(cfg, *, slots=8, cache_len=4096, prefill_len=2048,
                prefill_chunk=512, block_size=128, requests=8,
                min_prompt=256, max_new=32, seed=0, check_kernels=True,
                log=print) -> dict:
    """Serve seeded requests through ``Engine``, then replay the longest
    one's logits through the same paged path against the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import build_model
    from repro.models.reference import dense_logits_f32
    from repro.serving import Engine, SamplingParams

    t0 = time.perf_counter()
    model = build_model(cfg, remat="none")
    params = model.init(jax.random.key(seed), dtype=jnp.bfloat16)
    engine = Engine(model, params, slots=slots, prefill_len=prefill_len,
                    cache_len=cache_len, prefill_chunk=prefill_chunk,
                    block_size=block_size)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, cfg.vocab_size,
                            int(rng.integers(min_prompt, prefill_len + 1)),
                            dtype=np.int32) for _ in range(requests)]
    for i, p in enumerate(prompts):
        engine.submit(p, SamplingParams(max_new_tokens=max_new, seed=seed + i))
    t_serve = time.perf_counter()
    results = engine.run(max_ticks=10 * max_new)
    serve_s = time.perf_counter() - t_serve
    done = [r for r in results.values() if len(r.tokens) == max_new]
    if len(done) != requests:
        raise RuntimeError(f"served {len(done)}/{requests} requests in full")
    log(f"serve: {requests} requests, prompts {min(map(len, prompts))}-"
        f"{max(map(len, prompts))} tokens, {max_new} new each, "
        f"{engine.ticks} decode ticks in {serve_s:.1f} s")

    kernels = {}
    if check_kernels:
        rid = max(range(requests), key=lambda i: len(prompts[i]))
        S = len(prompts[rid])
        Sp = engine._bucket_len(S)
        bt = engine.pool.block_tables[:1]
        kernels["prefill"] = _has_kernel(engine._prefix_prefill, params, {
            "tokens": jnp.zeros((1, Sp), jnp.int32),
            "positions": jnp.zeros((1, Sp), jnp.int32),
            "length": jnp.ones((1,), jnp.int32),
            "block_tables": jnp.asarray(bt)}, engine.cache)
        z = jnp.zeros((slots,), jnp.int32)
        kernels["decode"] = _has_kernel(
            engine._generate, params, engine.cache, z, z,
            jnp.zeros((slots,), jnp.uint32), z,
            jnp.zeros((slots,), jnp.float32), z,
            jnp.ones((slots,), jnp.float32),
            block_tables=jnp.asarray(engine.pool.block_tables))

    # replay the longest request through the paged prefill + decode path
    # on a fresh pool (greedy tokens fed back), then compare its logits
    rid = max(range(requests), key=lambda i: len(prompts[i]))
    prompt, gen = prompts[rid], results[rid].tokens
    S = len(prompt)
    engine.cache = None                          # free the served pool
    cache = model.init_cache(slots, cache_len,
                             paged=(engine.num_blocks, engine.block_size))
    bt = np.full((1, engine.max_blocks), -1, np.int32)
    used = -(-(S + max_new) // engine.block_size)
    bt[0, :used] = np.arange(used)
    Sp = engine._bucket_len(S)
    toks = np.zeros(Sp, np.int32)
    toks[:S] = prompt
    pos = np.where(np.arange(Sp) < S, np.arange(Sp), -1).astype(np.int32)
    logits, cache = engine._prefix_prefill(params, {
        "tokens": jnp.asarray(toks)[None], "positions": jnp.asarray(pos)[None],
        "length": jnp.asarray([S], jnp.int32),
        "block_tables": jnp.asarray(bt)}, cache)
    got = [np.asarray(logits[0], np.float32)]
    decode = jax.jit(model.decode_step)
    for t in range(max_new - 1):
        p = S + t
        logits, cache = decode(params, {
            "tokens": jnp.asarray([[gen[t]]], jnp.int32),
            "positions": jnp.asarray([[p]], jnp.int32),
            "pos_row": jnp.asarray([p], jnp.int32),
            "block_tables": jnp.asarray(bt)}, cache)
        got.append(np.asarray(logits[0], np.float32))
    got = np.stack(got)
    del cache
    ref = dense_logits_f32(params, cfg, np.concatenate([prompt, gen[:-1]]),
                           rows=range(S - 1, S + max_new - 1))
    ref_rms = float(np.sqrt(np.mean(ref ** 2)))
    err = got - ref
    rel_rms = float(np.sqrt(np.mean(err ** 2))) / ref_rms
    max_abs = float(np.abs(err).max()) / ref_rms
    ok = rel_rms <= LOGIT_REL_RMS_TOL and max_abs <= LOGIT_MAX_ABS_TOL
    log(f"serve: logits of rid {rid} (prompt {S}, {max_new} positions) vs "
        f"float32 reference: rel RMS err {rel_rms:.5f} (tol "
        f"{LOGIT_REL_RMS_TOL}), max abs err {max_abs:.5f} x ref RMS (tol "
        f"{LOGIT_MAX_ABS_TOL}) -> {'ok' if ok else 'FAIL'}")
    return {"ok": ok, "seconds": time.perf_counter() - t0,
            "serve_seconds": serve_s, "kernels": kernels,
            "logit_rel_rms": rel_rms, "logit_max_abs": max_abs}


def train_phase(cfg, *, seq=4096, batch=1, steps=3, plan=None, seed=0,
                check_kernels=True, log=print) -> dict:
    """Run ``steps`` Trainer steps; the loss must be finite and fall."""
    import jax
    import jax.numpy as jnp

    from repro.core.config import (OptimizerConfig, ParallelConfig,
                                   RunConfig, ShapeConfig, StepKind)
    from repro.core.telemetry import RunTelemetry
    from repro.train.runtime import Trainer

    t0 = time.perf_counter()
    shape = ShapeConfig("smoke", seq, batch, StepKind.TRAIN)
    run_cfg = RunConfig(
        model=cfg, shape=shape, parallel=ParallelConfig(remat="full"),
        optimizer=OptimizerConfig(lr=TRAIN_LR, warmup_steps=0,
                                  total_steps=steps),
        seed=seed)
    telem = RunTelemetry(None, cfg, shape,
                         n_chips=plan.chips if plan is not None else 1,
                         device_kind=jax.devices()[0].device_kind)
    trainer = Trainer(run_cfg, plan=plan, telemetry=telem)
    report = trainer.run(steps)
    losses = report.losses
    kernels = {}
    if check_kernels:
        b = {"tokens": jnp.zeros((batch, seq), jnp.int32),
             "labels": jnp.zeros((batch, seq), jnp.int32)}
        # the step traces as it ran: under the plan's mesh and rules
        with (plan.activate(trainer.mesh) if plan is not None
              else contextlib.nullcontext()):
            kernels["train"] = _has_kernel(trainer.step_fn,
                                           trainer.train_state, b)
    ok = all(math.isfinite(x) for x in losses) and losses[-1] < losses[0]
    step_s = [round(r["step_s"], 3) for r in telem.records]
    log(f"train: {steps} steps at B={batch} T={seq}"
        f"{' plan ' + plan.name if plan is not None else ''}: losses "
        f"{[round(x, 4) for x in losses]}, step wall s {step_s} -> "
        f"{'ok' if ok else 'FAIL'}")
    del trainer
    gc.collect()
    return {"ok": ok, "seconds": time.perf_counter() - t0, "losses": losses,
            "kernels": kernels}


# ---------------------------------------------------------------------------
def _phase(name, fn, clock, log=print):
    c0 = clock.seconds
    out = fn()
    log(f"{name}: wall {out['seconds']:.1f} s, compile {clock.seconds - c0:.1f}"
        f" s, device peak memory so far {_peak_gib():.2f} GiB, Pallas "
        f"kernel in compiled steps {out['kernels']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _repro()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    from repro.core.compile_cache import enable_compilation_cache
    print(f"compilation cache: {enable_compilation_cache()}")
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    print(f"device: {dev}")
    clock = CompileClock()

    train_cfg = qwen3(layers=1, vocab_divisor=8)
    print(f"train config: qwen3-32b widths, depth cut 64 -> 1 layer, vocab "
          f"cut {train_cfg.vocab_size * 8} -> {train_cfg.vocab_size}, "
          f"{train_cfg.param_count() / 1e9:.3f} B params")
    if args.chips == 4:
        from repro.parallel.plan import resolve_plan
        from repro.core.config import ShapeConfig, StepKind
        plan = resolve_plan("model=4", train_cfg, chips=4,
                            shape=ShapeConfig("smoke", 4096, 1,
                                              StepKind.TRAIN))
        tp = _phase("train model=4", lambda: train_phase(
            train_cfg, plan=plan, seed=args.seed), clock)
        one = _phase("train 1 chip", lambda: train_phase(
            train_cfg, seed=args.seed), clock)
        diff = abs(tp["losses"][0] - one["losses"][0])
        tp_ok = diff <= TP_LOSS_TOL
        print(f"tp: step-1 loss model=4 {tp['losses'][0]:.6f} vs one chip "
              f"{one['losses'][0]:.6f}: |diff| {diff:.6f} (tol "
              f"{TP_LOSS_TOL}) -> {'ok' if tp_ok else 'FAIL'}")
        ok = tp_ok and tp["ok"] and one["ok"] and all(
            tp["kernels"].values()) and all(one["kernels"].values())
    else:
        serve_cfg = qwen3(layers=4)
        print(f"serve config: qwen3-32b widths, depth cut 64 -> 4 layers, "
              f"full vocab {serve_cfg.vocab_size}, bf16, "
              f"{serve_cfg.param_count() / 1e9:.3f} B params")
        serve = _phase("serve", lambda: serve_phase(serve_cfg,
                                                    seed=args.seed), clock)
        gc.collect()
        train = _phase("train", lambda: train_phase(train_cfg,
                                                    seed=args.seed), clock)
        ok = serve["ok"] and train["ok"] and all(
            serve["kernels"].values()) and all(train["kernels"].values())
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
