"""Training driver: the full host-side control plane on the paper's runtime.

Per step s the engine spawns/uses:
  prefetch(s)   WRITES ("batch", s)         (DataPipeline)
  step(s)       READS ("batch", s), RW "train_state"
  metrics(s)    READS ("metrics", s)
  ckpt every K  READS "train_state" -> async write/commit chain

The ASM dependency system serializes steps through "train_state" while
prefetch and checkpoint I/O overlap freely — the paper's fine-grained
synchronization replacing a global loop lock. Heartbeats + stragglers feed
the FT layer; on failure the engine restores the last committed checkpoint
(restart-from-checkpoint is exercised in tests/test_integration.py).

CLI (CPU smoke): PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
    --smoke --steps 20 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.core import TaskRuntime, Tracer
from repro.data import DataPipeline, TokenSource
from repro.data.pipeline import batch_addr
from repro.dist.partitioning import make_sharder
from repro.ft import HeartbeatMonitor, StragglerMitigator
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (TrainConfig, abstract_train_state,
                                batch_spec, init_train_state,
                                make_train_step)
from repro.optim import AdamWConfig


def _shardings(abstract):
    """The sharding tree of a tree of sharded ShapeDtypeStructs."""
    return jax.tree_util.tree_map(lambda a: a.sharding, abstract)


class TrainEngine:
    def __init__(self, cfg, *, batch_size=8, seq_len=64, mesh=None,
                 runtime=None, ckpt_dir=None, ckpt_every=0, tracer=None,
                 opt=None, microbatches=1, seed=0):
        self.cfg = cfg
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.mesh = mesh
        self.sh = make_sharder(mesh, kind="train", global_batch=batch_size)
        self.rt = runtime or TaskRuntime(n_workers=3, tracer=tracer).start()
        tc = TrainConfig(microbatches=microbatches,
                         optimizer=opt or AdamWConfig(lr=1e-3, warmup_steps=5))
        self.tc = tc
        # On a mesh the state is built directly in its sharded layout (FSDP:
        # weights and AdamW moments split over "data") and the step keeps
        # it there; without one, everything lives on the default device.
        init_kw, step_kw, self._batch_sh = {}, {}, None
        if mesh is not None:
            state_sh = _shardings(abstract_train_state(cfg, self.sh))
            self._batch_sh = _shardings(batch_spec(
                cfg, ShapeConfig("train", seq_len, batch_size, "train"),
                self.sh))
            init_kw = {"out_shardings": state_sh}
            step_kw = {"in_shardings": (state_sh, self._batch_sh),
                       "out_shardings": (state_sh,
                                         NamedSharding(mesh, P()))}
        self.step_fn = jax.jit(make_train_step(cfg, self.sh, tc),
                               donate_argnums=(0,), **step_kw)
        self.state = jax.jit(init_train_state, static_argnums=(0, 2),
                             **init_kw)(cfg, jax.random.PRNGKey(seed),
                                        tc.optimizer)
        frames_dim = cfg.d_model if cfg.family == "encdec" else None
        self.pipe = DataPipeline(
            self.rt, TokenSource(cfg.vocab_size, seed=seed), batch_size,
            seq_len, prefetch=2, frames_dim=frames_dim,
            frames_ratio=cfg.encoder_frames_ratio).start()
        self.ckpt = (CheckpointManager(ckpt_dir, self.rt)
                     if ckpt_dir else None)
        self.ckpt_every = ckpt_every
        self.hb = HeartbeatMonitor(timeout_s=30.0).start()
        self.straggler = StragglerMitigator()
        self.history: list[dict] = []
        self.start_step = int(self.state["step"])

    # ------------------------------------------------------------- steps
    def _device_batch(self, raw):
        if self._batch_sh is None:
            return {k: jnp.asarray(v) for k, v in raw.items()}
        return {k: jax.device_put(v, self._batch_sh[k])
                for k, v in raw.items()}

    def run(self, n_steps: int, log_every: int = 10, inject_failure_at=None):
        s0 = int(self.state["step"])
        this_run: list[dict] = []
        for s in range(s0, s0 + n_steps):
            t0 = time.monotonic()
            raw = self.pipe.get(s)
            batch = self._device_batch(raw)

            def do_step(batch=batch):
                with self.rt.tracer.span("step", s):
                    self.state, metrics = self.step_fn(self.state, batch)
                return {k: float(v) for k, v in metrics.items()}

            t = self.rt.spawn(do_step, name=f"step:{s}",
                              reads=[batch_addr(s)], rw=["train_state"],
                              retain=True)
            self.rt.taskwait(t, timeout=600)
            if t.exception:
                raise t.exception
            m = t.result
            m["step"] = s
            m["wall_s"] = time.monotonic() - t0
            self.history.append(m)
            this_run.append(m)
            self.hb.beat("trainer")
            self.straggler.record("trainer", m["wall_s"])
            if self.ckpt and self.ckpt_every and (s + 1) % self.ckpt_every == 0:
                self.ckpt.save_async(self.state, s + 1)
            if inject_failure_at is not None and s == inject_failure_at:
                raise RuntimeError("injected failure (test)")
            if log_every and s % log_every == 0:
                print(f"step {s:5d} loss={m['loss']:.4f} "
                      f"gnorm={m['grad_norm']:.3f} {m['wall_s']*1e3:.0f}ms",
                      flush=True)
        return this_run

    def restore_latest(self):
        assert self.ckpt is not None
        self.rt.barrier(timeout=120)  # let pending saves commit
        state, step = self.ckpt.restore()
        state["step"] = jnp.asarray(state["step"])
        self.state = state
        return step

    def close(self):
        self.rt.barrier(timeout=120)
        self.hb.stop()
        self.rt.shutdown()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--trace-dir", default=None)
    args = ap.parse_args()

    setup_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    tracer = Tracer(enabled=bool(args.trace_dir), out_dir=args.trace_dir)
    mesh = make_host_mesh()
    eng = TrainEngine(cfg, batch_size=args.batch, seq_len=args.seq, mesh=mesh,
                      ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      runtime=TaskRuntime(n_workers=3, tracer=tracer).start())
    hist = eng.run(args.steps)
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"loss: {first:.4f} -> {last:.4f} "
          f"({'improved' if last < first else 'NOT improved'})")
    eng.close()
    if args.trace_dir:
        tracer.flush()


if __name__ == "__main__":
    main()
