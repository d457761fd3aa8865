#!/usr/bin/env python3
"""Bring-up smoke on a TPU: the serving main path at Qwen3-1.7B's full width.

    python3 chip_smoke.py             # one chip: serve phase, kernel phase
    python3 chip_smoke.py --chips 4   # four chips: FSDP TrainEngine phase only

One chip: random bf16 weights from ``--seed`` at the published width
(28 layers, d_model 2048, vocab 151936) behind ``ServeEngine`` on
``TaskRuntime``; 16 requests from 2 client threads, prompts of 128, 512 and
2048 tokens, 32 new tokens each, on 8 slots of a 4096-position cache. Every
request must return its full token count, the engine raises on non-finite
logits, the drain must not re-raise, and one request's tokens are checked
against a float32 forward of the same weights. Then ``flash_attention`` at
Qwen3 widths and ``ssd_chunked_pallas`` at Mamba2-1.3B widths run compiled
(not interpreted) against ``repro.kernels.ref``.

Four chips: ``TrainEngine`` at full width on a (data=4) mesh with FSDP
builds its state sharded, takes 3 steps, and its step-0 loss is compared
with a single-device forward of the same weights.

Earlier lines of output are smoke diagnostics, not benchmark metrics. The
last line is ``{"ok": true, "device": {...}}``; it is printed only when
every phase passed. Without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import TaskRuntime  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.kernels.ref import attention_ref, ssm_ref  # noqa: E402
from repro.kernels.ssd import ssd_chunked_pallas  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.models import api as mapi  # noqa: E402
from repro.models.common import cast_params, dtype_of  # noqa: E402
from repro.models.params import init_params  # noqa: E402
from repro.serve import ServeEngine  # noqa: E402

ARCH = "qwen3-1.7b"


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def diag(**kw) -> None:
    """One line of smoke diagnostics (not a benchmark metric)."""
    print("smoke-diag " + json.dumps(kw, default=str), flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, by listener."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            self.compiles += event.endswith("backend_compile_duration")


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def bf16_params(cfg, seed: int):
    """Weights drawn leaf by leaf in float32 and cast inside one program,
    so no float32 copy of the model is ever held."""
    init = jax.jit(lambda k: cast_params(init_params(cfg, k), dtype_of(cfg)))
    return jax.block_until_ready(init(jax.random.PRNGKey(seed)))


# ------------------------------------------------------------------ serve
def serve_phase(cfg, seed: int, *, n_slots=8, max_seq=4096,
                prompt_lens=(128, 512, 2048), n_clients=2,
                per_client=8, max_new=32, check_len=128):
    t0 = time.perf_counter()
    params = bf16_params(cfg, seed)
    diag(phase="serve", step="init_params", wall_s=time.perf_counter() - t0,
         n_params=sum(x.size for x in jax.tree_util.tree_leaves(params)))

    rt = TaskRuntime(n_workers=3).start()
    eng, finished = None, False
    try:
        eng = ServeEngine(cfg, params, rt, n_slots=n_slots,
                          max_seq=max_seq).start()
        del params
        reqs, lock = [], threading.Lock()

        def client(cid):
            rng = np.random.default_rng([seed, cid])
            mine = []
            for i in range(per_client):
                n = prompt_lens[(cid + i) % len(prompt_lens)]
                prompt = rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
                mine.append(eng.submit(prompt, max_new_tokens=max_new))
            with lock:
                reqs.extend(mine)
            for req in mine:
                eng.wait(req, timeout=600)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(c,), daemon=True)
                   for c in range(n_clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=650)
            check(not t.is_alive(), "a client thread did not finish")
        wall = time.perf_counter() - t0
        check(eng.stop(timeout=300), "engine drain timed out")  # re-raises
        check(len(reqs) == n_clients * per_client,
              f"{len(reqs)} requests submitted")
        for r in reqs:
            check(not r.rejected, f"request {r.id} was rejected")
            check(len(r.tokens) == max_new + 1,
                  f"request {r.id}: {len(r.tokens)} tokens, "
                  f"expected {max_new + 1}")
            check(all(0 <= t < cfg.vocab_padded for t in r.tokens),
                  f"request {r.id}: token out of range")
        diag(phase="serve", step="requests", wall_s=wall,
             requests=len(reqs), tokens_per_request=max_new + 1,
             prompt_lens=sorted({len(r.prompt) for r in reqs}),
             engine_stats=eng.stats,
             peak_bytes=peak_bytes(jax.devices()[0]))

        eng.cache = None  # room for the float32 reference
        ref = next(r for r in reqs if len(r.prompt) == check_len)
        margin = teacher_forced_margin(cfg, eng.params, ref)
        diag(phase="serve", step="reference", request=ref.id,
             worst_margin_in_logit_std=margin)
        # A greedy token of the bf16 engine must be (near) the float32
        # reference's top choice at every position. 0.25 std covers bf16
        # rounding near ties; an arbitrary token sits ~4.5 std below the
        # top of 151936 logits.
        check(margin <= 0.25, f"request {ref.id} strays from the float32 "
                              f"reference by {margin:.3f} logit std")
        rt.shutdown()
        finished = True
    finally:
        if not finished:
            if eng is not None:
                eng.stop(drain=False)
            with contextlib.suppress(Exception):
                rt.shutdown(wait=False)


def teacher_forced_margin(cfg, params, req) -> float:
    """Run the prompt plus the engine's tokens through a float32 forward of
    the same weights; return the worst gap, in units of that position's
    logit std, between the reference's best logit and the logit of the
    token the engine chose."""
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    seq = np.concatenate([req.prompt, np.asarray(req.tokens[:-1], np.int32)])
    n_prompt = len(req.prompt)

    @jax.jit
    def ref_logits(p, tokens):
        with jax.default_matmul_precision("highest"):
            logits, _, _ = mapi.forward(cfg32, p, {"tokens": tokens},
                                        mode="train")
        return logits[0, n_prompt - 1:]

    p32 = cast_params(params, jnp.float32)
    logits = np.asarray(ref_logits(p32, jnp.asarray(seq)[None]))
    del p32
    check(np.isfinite(logits).all(), "reference logits are not finite")
    chosen = logits[np.arange(len(req.tokens)), np.asarray(req.tokens)]
    gap = (logits.max(axis=-1) - chosen) / logits.std(axis=-1)
    return float(gap.max())


# ------------------------------------------------------------------ kernels
def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(out - ref).max() / np.abs(ref).max())


def kernel_phase(seed: int):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    # bf16 inputs, f32 accumulation: outputs agree to a few bf16 ulps of
    # the largest value; the SSD recurrence runs 2048 steps, so it is
    # allowed more
    tol_flash, tol_ssd = 2e-2, 5e-2

    t0 = time.perf_counter()
    q = jax.random.normal(ks[0], (1, 2048, 16, 128), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2048, 8, 128), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2048, 8, 128), jnp.bfloat16)
    out = jax.block_until_ready(flash_attention(q, k, v, interpret=False))
    with jax.default_matmul_precision("highest"):
        ref = attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                            v.astype(jnp.float32))
    err = rel_err(out, ref)
    diag(phase="kernels", kernel="flash_attention", rel_err=err,
         tol=tol_flash, wall_s=time.perf_counter() - t0)
    check(np.isfinite(np.asarray(out, np.float32)).all(),
          "flash_attention output not finite")
    check(err <= tol_flash, f"flash_attention rel err {err} > {tol_flash}")

    t0 = time.perf_counter()
    b, l, h, p, n = 1, 2048, 64, 64, 128
    x = jax.random.normal(ks[3], (b, l, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, l, h))) * 0.1
    A = -jnp.exp(jax.random.uniform(ks[5], (h,), minval=0.0, maxval=1.0))
    B = jax.random.normal(ks[6], (b, l, n), jnp.bfloat16)
    C = jax.random.normal(ks[7], (b, l, n), jnp.bfloat16)
    y, fs = jax.block_until_ready(
        ssd_chunked_pallas(x, dt, A, B, C, chunk=256, interpret=False))
    with jax.default_matmul_precision("highest"):
        yr, fsr = ssm_ref(x, dt, A, B, C)
    err_y, err_s = rel_err(y, yr), rel_err(fs, fsr)
    diag(phase="kernels", kernel="ssd_chunked_pallas", rel_err_y=err_y,
         rel_err_state=err_s, tol=tol_ssd, wall_s=time.perf_counter() - t0)
    check(np.isfinite(np.asarray(y, np.float32)).all(),
          "ssd_chunked_pallas output not finite")
    check(max(err_y, err_s) <= tol_ssd,
          f"ssd_chunked_pallas rel err {max(err_y, err_s)} > {tol_ssd}")


# ------------------------------------------------------------------ train
def train_phase(cfg, seed: int, *, batch=4, seq=512, steps=3):
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import TrainEngine

    devices = jax.devices()
    t0 = time.perf_counter()
    eng = TrainEngine(cfg, batch_size=batch, seq_len=seq,
                      mesh=make_host_mesh(data=len(devices)), seed=seed)
    try:
        leaves = jax.tree_util.tree_leaves(eng.state)
        for x in leaves:
            check(x.sharding.device_set == set(devices),
                  f"a state leaf {x.shape} is not on all {len(devices)} "
                  "devices")
        per_dev = {d.id: 0 for d in devices}
        for x in leaves:
            for s in x.addressable_shards:
                per_dev[s.device.id] += s.data.nbytes
        total = sum(x.nbytes for x in leaves)
        diag(phase="train", step="init_state", wall_s=time.perf_counter() - t0,
             state_bytes=total, bytes_per_device=per_dev,
             split_leaves=sum(not x.sharding.is_fully_replicated
                              for x in leaves), leaves=len(leaves))
        check(max(per_dev.values()) < 0.3 * total,
              "train state is not split across the devices")

        # step-0 loss is taken before the update: recompute it on one
        # device from the same weights in bf16 and the same batch
        tokens = eng.pipe.source.batch(0, batch, seq)
        ref_loss = single_device_loss(cfg, eng.state["params"], tokens,
                                      devices[0])
        t0 = time.perf_counter()
        hist = eng.run(steps, log_every=1)
        wall = time.perf_counter() - t0
        losses = [h["loss"] for h in hist]
        check(all(math.isfinite(x) for x in losses), f"losses {losses}")
        rel = abs(losses[0] - ref_loss) / abs(ref_loss)
        diag(phase="train", step="run", steps=steps, wall_s=wall,
             losses=losses, ref_step0_loss=ref_loss, rel_diff=rel,
             peak_bytes={d.id: peak_bytes(d) for d in devices})
        # bf16 compute in both; only the sharded reduction order differs
        check(rel <= 1e-2, f"step-0 loss {losses[0]} vs single-device "
                           f"{ref_loss} (rel {rel:.2e} > 1e-2)")
    finally:
        eng.close()


def single_device_loss(cfg, params, tokens, device) -> float:
    p = jax.device_put(cast_params(params, dtype_of(cfg)), device)
    t = jax.device_put(jnp.asarray(tokens), device)

    @jax.jit
    def loss(p, t):
        logits, aux, _ = mapi.forward(cfg, p, {"tokens": t}, mode="train")
        labels, mask = mapi.shift_labels(t)
        total, _ = mapi.loss_fn(cfg, logits, labels, mask)
        return total + cfg.moe_aux_loss_coef * aux

    return float(loss(p, t))


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + kernels on one chip; 4: the FSDP "
                         "train phase on four chips, nothing else")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: --chips 4 needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 2
    cache_dir = setup_compile_cache()
    clock = CompileClock()
    cfg = get_config(ARCH)
    diag(device_kind=dev.device_kind, devices=len(devices), arch=cfg.name,
         compile_cache=cache_dir, seed=args.seed)

    if args.chips == 1:
        phases = [("serve", lambda: serve_phase(cfg, args.seed)),
                  ("kernels", lambda: kernel_phase(args.seed))]
    else:
        phases = [("train", lambda: train_phase(cfg, args.seed))]
    for name, run in phases:
        t0, c0, n0 = time.perf_counter(), clock.seconds, clock.compiles
        run()
        diag(phase=name, wall_s=time.perf_counter() - t0,
             compile_s=clock.seconds - c0, compiles=clock.compiles - n0)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
