"""End-to-end training driver with CRUM fault tolerance.

Runs on anything from 1 CPU device (--smoke) to the production mesh; the
CheckpointedTrainer provides forked checkpointing, incremental persistence
and restart (examples/train_restart.py kills and resumes this loop).

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Rerunning the command resumes from the newest committed checkpoint and
checks the restored state against the digests the save recorded. The
platform is whatever JAX finds (``JAX_PLATFORMS`` decides); the lines
``[train] device {...}`` and ``[train] restore_check {...}`` say what ran.
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import DEFAULT_CODEC
from repro.configs import get_config, list_archs
from repro.core import (
    CheckpointedTrainer,
    CheckpointPolicy,
    PreemptionHandler,
    list_persist_backends,
)
from repro.data import SyntheticBatches
from repro.kernels.ops import auto_dispatch
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build
from repro.obs import trace as obs_trace
from repro.optim import get_optimizer, warmup_cosine
from repro.runtime.env import device_report, enable_compile_cache, keep_off_device
from repro.runtime.sharding import ShardingRules
from repro.runtime.steps import make_train_step
from repro.utils.tree import flatten_with_paths, unflatten_from_paths


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--codec", default=DEFAULT_CODEC)
    ap.add_argument(
        "--backend", choices=list_persist_backends(), default="thread",
        help="persist backend: 'fork' = paper's COW child, 'thread' = pool",
    )
    ap.add_argument(
        "--device-runner", choices=["inline", "proxy"], default="inline",
        help="inline: step fn runs in-process; proxy: the paper's "
             "architecture — compute in a restartable proxy process with "
             "API log-and-replay recovery",
    )
    ap.add_argument(
        "--device-capacity", default=None, metavar="BYTES|PCT%",
        help="managed-memory (UVM) mode: hard device budget for the model "
             "state, either absolute bytes or a percentage of the state "
             "size (e.g. '50%%' = oversubscription ratio 2x). Pages "
             "migrate on fault; the checkpointer syncs page deltas",
    )
    ap.add_argument("--page-bytes", type=int, default=None,
                    help="managed-memory page size (default 64 KiB)")
    ap.add_argument("--eviction-policy", choices=["lru", "clock"],
                    default="lru", help="managed-memory eviction policy")
    ap.add_argument("--promote-threshold", type=int, default=0,
                    help="Volta-style access-counter promotion: a HOST page "
                         "read this many times within --promote-window is "
                         "migrated to device; colder reads are served "
                         "remotely without a migration (0/1 = migrate on "
                         "first touch)")
    ap.add_argument("--promote-window", type=int, default=0,
                    help="promotion counting window in ticks (0 = unbounded)")
    ap.add_argument("--no-incremental", action="store_true")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--obs-dir", default=None, metavar="DIR",
                    help="enable observability: trace shards and metrics "
                         "snapshots land here (the proxy process inherits "
                         "the setting; merge with "
                         "`python -m repro.obs.report DIR`)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.obs_dir:
        obs_trace.enable(args.obs_dir, "app")

    if args.device_runner == "proxy":
        return _main_proxy(args)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build(cfg)
    mesh = (
        make_production_mesh()
        if args.production_mesh
        else make_host_mesh((jax.device_count(),), ("data",))
    )
    print(f"[train] device {json.dumps({**device_report(), 'digest': auto_dispatch()})}",
          flush=True)
    rules = ShardingRules(cfg=cfg, mesh=mesh)
    optimizer = get_optimizer(
        cfg.optimizer, warmup_cosine(args.lr, 10, args.steps)
    )

    trainer = CheckpointedTrainer(
        None,  # set below
        store_root=args.ckpt_dir,
        policy=CheckpointPolicy(interval_steps=args.ckpt_every, keep_last=2),
        codec=args.codec,
        incremental=not args.no_incremental,
        chunk_bytes=1 << 20,
        backend=args.backend,
        page_bytes=args.page_bytes,
        eviction_policy=args.eviction_policy,
        promote_threshold=args.promote_threshold,
        promote_window=args.promote_window,
    )
    preempt = PreemptionHandler(trainer.policy).install()

    step_fn, state_shardings, batch_sh = make_train_step(
        model, rules, optimizer, donate=False
    )
    trainer.train_step = step_fn

    @functools.partial(jax.jit, out_shardings=state_shardings)
    def init_device():
        # built where the step expects it, shard by shard
        params = model.init(jax.random.key(0))
        return {
            "params": params,
            "opt": optimizer.init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    def init_state():
        return {
            "device": init_device(),
            "host": {
                "step": np.int64(0),
                "data": SyntheticBatches(
                    cfg, batch=args.batch, seq_len=args.seq
                ).state(),
            },
        }

    def sharding_for(path, shape):
        flat_sh, _ = flatten_with_paths(
            {"device": state_shardings, "host": None}
        )
        return flat_sh.get(path)

    # a resume re-digests what it placed, on the device, against the save
    state, start = trainer.resume_or(
        init_state, sharding_for=sharding_for, verify="device"
    )
    if start:
        check = {"step": start, **trainer.restorer.last_check,
                 "seconds": trainer.timings.totals["restore/verify_device"]}
        print(f"[train] restore_check {json.dumps(check)}", flush=True)
    data = SyntheticBatches.from_state(
        cfg, batch=args.batch, seq_len=args.seq, state=state["host"]["data"]
    )
    print(f"[train] arch={cfg.name} start_step={start} mesh={dict(mesh.shape)}")

    if args.device_capacity is not None:
        return _run_managed(args, trainer, state, start, data, preempt)

    tr = obs_trace.get()
    step = start
    t_log, logged = time.perf_counter(), start
    for _ in range(args.steps - start):
        t0 = time.perf_counter() if tr is not None else 0.0
        batch = jax.tree.map(jnp.asarray, next(data))
        state["device"], metrics = step_fn(state["device"], batch)
        step += 1
        if tr is not None:
            tr.complete("app.step", t0, step=step)
        state["host"]["step"] = np.int64(step)
        state["host"]["data"] = data.state()
        if step % args.log_every == 0 or step == args.steps:
            loss = float(metrics["loss"])  # waits for the step
            now = time.perf_counter()
            print(
                f"[train] step={step} loss={loss:.4f} "
                f"grad_norm={float(metrics['grad_norm']):.3f} "
                f"step_s={(now - t_log) / (step - logged):.3f}",
                flush=True,
            )
            t_log, logged = now, step
        if trainer.policy.should_checkpoint(step):
            r = trainer.checkpoint_now(step, state)
            print(
                f"[ckpt] step={step} blocking={r.blocking_s*1e3:.1f}ms "
                f"(persist continues in background)",
                flush=True,
            )
        if preempt.received.is_set():
            print("[train] preemption: checkpointing and exiting")
            if _needs_preempt_ckpt(trainer, step):
                trainer.checkpoint_now(step, state)
            break

    done = trainer.finish()
    for r in done:
        print(
            f"[ckpt-done] step={r.step} blocking={r.blocking_s*1e3:.1f}ms "
            f"persist={r.persist_s*1e3:.1f}ms written={r.chunks_written} "
            f"reused={r.chunks_reused}"
        )
    preempt.uninstall()
    print(json.dumps({
        "final_step": step,
        "peak_bytes_in_use": _peak_device_bytes(),
        "timings": trainer.timings.summary(),
    }, indent=2))
    return 0


def _peak_device_bytes() -> int | None:
    """Peak bytes in use on the busiest device, where the backend says."""
    stats = [d.memory_stats() for d in jax.local_devices()]
    peaks = [s["peak_bytes_in_use"] for s in stats if s and "peak_bytes_in_use" in s]
    return max(peaks) if peaks else None


def _tree_nbytes(tree) -> int:
    flat, _ = flatten_with_paths(tree)
    return sum(int(np.asarray(l).nbytes) for l in flat.values())


def _needs_preempt_ckpt(trainer, step: int) -> bool:
    """SIGTERM sets the policy's preempt flag too, so the train loop may
    already have checkpointed this very step before exiting — saving it
    again would run two concurrent persists of the same step directory."""
    return not trainer.results or trainer.results[-1].step != step


def _resolve_capacity(spec: str, state_nbytes: int) -> int:
    """'BYTES' or 'PCT%' (of the device state size) -> absolute bytes."""
    s = str(spec).strip()
    if s.endswith("%"):
        return max(1, int(state_nbytes * float(s[:-1]) / 100.0))
    return int(s)


def _run_managed(args, trainer, state, start, data, preempt) -> int:
    """Inline training through a ManagedSpace (the UVM oversubscription
    path): the device budget is hard, pages migrate on fault, and the
    checkpointer syncs page deltas instead of digest-scanning every leaf."""
    state_nbytes = _tree_nbytes(state["device"])
    cap = _resolve_capacity(args.device_capacity, state_nbytes)
    trainer.device_capacity_bytes = cap
    print(f"[uvm] device_capacity={cap}B state={state_nbytes}B "
          f"oversubscription=x{state_nbytes / cap:.2f} "
          f"policy={args.eviction_policy}", flush=True)

    def batches():
        while True:
            yield jax.tree.map(jnp.asarray, next(data))

    def on_metrics(step, metrics):
        state["host"]["data"] = data.state()
        if step % args.log_every == 0 or step == args.steps:
            print(f"[train] step={step} loss={float(metrics['loss']):.4f}",
                  flush=True)

    state = trainer.run(
        state, batches(), num_steps=args.steps - start, start_step=start,
        on_metrics=on_metrics, stop=preempt.received.is_set,
    )
    step = int(np.asarray(state["host"]["step"]))
    if preempt.received.is_set() and _needs_preempt_ckpt(trainer, step):
        print("[train] preemption: checkpointing and exiting", flush=True)
        trainer.checkpoint_now(step, trainer.materialize(state))
    done = trainer.finish()
    for r in done:
        print(
            f"[ckpt-done] step={r.step} blocking={r.blocking_s*1e3:.1f}ms "
            f"synced={r.chunks_synced} clean={r.chunks_clean} "
            f"written={r.chunks_written} reused={r.chunks_reused}"
        )
    preempt.uninstall()
    print(json.dumps({
        "final_step": step,
        "paging": trainer.paging_stats(),
        "timings": trainer.timings.summary(),
    }, indent=2))
    return 0


def _main_proxy(args) -> int:
    """The paper's architecture: this process never runs the step function.

    A ``train_arch`` step program (rebuilt from the CLI config inside the
    proxy — programs are replayable specs, not closures) executes in a
    supervised proxy process; this process forwards pipelined STEP calls,
    syncs the host mirror at checkpoint boundaries, and persists it with
    the same forked checkpointer. Batches are deterministic in the step
    number, which is what makes kill-replay recovery bit-identical.

    This process stays on the host CPU: the proxy owns the accelerator.
    """
    keep_off_device()
    program = {
        "name": "train_arch",
        "arch": args.arch,
        "smoke": bool(args.smoke),
        "batch": args.batch,
        "seq": args.seq,
        "lr": args.lr,
        "total_steps": args.steps,
    }
    capacity = None
    if args.device_capacity is not None:
        spec = str(args.device_capacity).strip()
        if spec.endswith("%"):
            # percentage of the program's device state, sized abstractly
            # (eval_shape): the app must never materialize the state it is
            # keeping out of its own process
            from repro.proxy.programs import make_program

            nbytes = make_program(program).state_nbytes()
            capacity = _resolve_capacity(spec, nbytes)
            print(f"[uvm] proxy device_capacity={capacity}B "
                  f"state={nbytes}B", flush=True)
        else:
            capacity = int(spec)
    trainer = CheckpointedTrainer(
        None,
        store_root=args.ckpt_dir,
        policy=CheckpointPolicy(interval_steps=args.ckpt_every, keep_last=2),
        codec=args.codec,
        incremental=not args.no_incremental,
        chunk_bytes=1 << 20,
        backend=args.backend,
        device_runner="proxy",
        program=program,
        device_capacity_bytes=capacity,
        page_bytes=args.page_bytes,
        eviction_policy=args.eviction_policy,
        promote_threshold=args.promote_threshold,
        promote_window=args.promote_window,
    )
    preempt = PreemptionHandler(trainer.policy).install()

    def init_state():
        # device side is None: resume_or lets the runner ask the program
        # for a deterministic init inside this process (shared registry)
        return {"device": None, "host": {"step": np.int64(0)}}

    state, start = trainer.resume_or(init_state)
    print(f"[train] arch={args.arch} device_runner=proxy start_step={start} "
          f"proxy_pid={trainer.runner.proxy.pid}", flush=True)
    print(f"[train] proxy device {json.dumps(trainer.runner.device)}", flush=True)

    def on_metrics(step, metrics):
        loss = metrics.get("loss")
        loss_s = f"{loss:.4f}" if loss is not None else "n/a"
        print(f"[train] step={step} loss={loss_s} "
              f"proxy_restarts={trainer.runner.restarts}", flush=True)

    state = trainer.run(
        state, num_steps=args.steps - start, start_step=start,
        on_metrics=on_metrics, stop=preempt.received.is_set,
    )
    step = int(np.asarray(state["host"]["step"]))
    if preempt.received.is_set() and _needs_preempt_ckpt(trainer, step):
        print("[train] preemption: checkpointing and exiting", flush=True)
        trainer.checkpoint_now(step, state)
    done = trainer.finish()
    for r in done:
        print(
            f"[ckpt-done] step={r.step} blocking={r.blocking_s*1e3:.1f}ms "
            f"persist={r.persist_s*1e3:.1f}ms written={r.chunks_written} "
            f"reused={r.chunks_reused}"
        )
    preempt.uninstall()
    print(f"[train] app device {json.dumps(device_report())}", flush=True)
    print(json.dumps({"final_step": step, "timings": trainer.timings.summary()},
                     indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
