#!/usr/bin/env python3
"""Bring-up check: the main path at full qwen2-0.5b width on TPU.

    python chip_smoke.py             # one chip: train -> checkpoint ->
                                     # restart -> serve, then proxied training
    python chip_smoke.py --chips 4   # the 4-chip data mesh: train, save,
                                     # resume, and the same steps on one device

Every phase is a child process of a real entry point (``repro.launch.train``
or ``repro.launch.serve``). This process never imports JAX, so the child
that needs the chip can take it. Each phase prints the platform, device
kind and count it ran on and which digest path ran; this script checks
them and the phase's results, and fails if any phase fails or runs on
anything but a TPU. Only then is the last line of standard output
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

Checkpoint images go to ``.chip_smoke/`` in the checkout, which is removed
at the start and at the end of a run.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
WORK = ROOT / ".chip_smoke"
ARCH = "qwen2-0.5b"
VOCAB = 151936  # qwen2-0.5b's published vocabulary
# one batch x sequence whose train step compiles to 14.3 GB on a 16 GB v5e
# (8 x 1024 needs 18.7 GB without buffer donation)
BATCH, SEQ = 4, 1024
# the default codec (pgzip) compresses 1 MiB chunks one at a time at about
# 20 MB/s, minutes for a 5 GB image; zstd1 persists it in seconds
CODEC = "zstd1"
DEADLINE_S = 1150.0
LOSS_RTOL = 1e-2  # bf16 epsilon is 2**-7
# the TPU runtime's own settings that give a process one chip of a host
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


class SmokeFailure(Exception):
    pass


def _child_env(extra: dict | None = None) -> dict:
    env = {**os.environ, **(extra or {})}
    env["PYTHONPATH"] = str(ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


def run_phase(name: str, argv: list[str], deadline: float,
              env: dict | None = None) -> str:
    """Run one child to completion in its own process group; echo and
    return its output. A child still alive at the deadline is killed with
    everything it started. ``env`` adds to the child's environment."""
    print(f"== {name}: {' '.join(argv)}", flush=True)
    t0 = time.perf_counter()
    proc = subprocess.Popen(
        [sys.executable, *argv], cwd=ROOT, env=_child_env(env),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        start_new_session=True,
    )
    try:
        out, _ = proc.communicate(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        _echo(name, out)
        raise SmokeFailure(f"{name}: still running at the deadline") from None
    finally:
        try:  # proxies and persist children outlive a dead parent otherwise
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    wall = time.perf_counter() - t0
    _echo(name, out)
    if proc.returncode != 0:
        raise SmokeFailure(f"{name}: exit code {proc.returncode}")
    print(f"== {name}: ok in {wall:.1f}s", flush=True)
    return out


def _echo(name: str, out: str) -> None:
    for line in out.splitlines():
        print(f"  {name} | {line}")


def tagged_json(out: str, tag: str) -> list:
    """Values of every ``<tag> {json}`` line."""
    pat = re.compile(rf"^{re.escape(tag)} (.*)$")
    return [json.loads(m.group(1)) for m in map(pat.match, out.splitlines()) if m]


def one_json(out: str, tag: str):
    vals = tagged_json(out, tag)
    if len(vals) != 1:
        raise SmokeFailure(f"expected one '{tag}' line, found {len(vals)}")
    return vals[0]


def losses(out: str) -> dict[int, float]:
    got = {
        int(m.group(1)): float(m.group(2))
        for m in re.finditer(r"^\[train\] step=(\d+) loss=(\S+)", out, re.M)
    }
    bad = {s: l for s, l in got.items() if not math.isfinite(l)}
    if not got or bad:
        raise SmokeFailure(f"losses missing or not finite: {got}")
    return got


def final_summary(out: str) -> dict:
    lines = out.splitlines()
    start = max(i for i, l in enumerate(lines) if l == "{")
    return json.loads("\n".join(lines[start:]))


def check_mesh(out: str, n: int) -> None:
    if not re.search(rf"^\[train\] arch=.* mesh=\{{'data': {n}\}}$", out, re.M):
        raise SmokeFailure(f"the train step did not run on a {n}-device data mesh")


def start_step(out: str) -> int:
    m = re.search(r"start_step=(\d+)", out)
    if m is None:
        raise SmokeFailure("no start_step line")
    return int(m.group(1))


def check_device(dev: dict | None, chips: int, *, digest: bool = True) -> dict:
    if not dev or dev.get("platform") != "tpu":
        raise SmokeFailure(f"ran on {dev}, not on a TPU")
    if dev.get("count") != chips:
        raise SmokeFailure(f"saw {dev.get('count')} devices, expected {chips}")
    if digest and dev.get("digest") != "pallas":
        raise SmokeFailure(f"digest path {dev.get('digest')!r}, not the Pallas kernel")
    return {k: dev[k] for k in ("platform", "kind", "count")}


def check_committed(ckpt: Path, steps: list[int]) -> None:
    for s in steps:
        if not (ckpt / f"step_{s:08d}" / "COMMIT").is_file():
            raise SmokeFailure(f"no committed checkpoint for step {s} in {ckpt}")


def check_restore(out: str, step: int) -> dict:
    if start_step(out) != step:
        raise SmokeFailure(f"resumed at {start_step(out)}, not at step {step}")
    rc = one_json(out, "[train] restore_check")
    if rc["step"] != step or rc["chunks"] <= 0 or rc["unmatched"]:
        raise SmokeFailure(f"restored state not verified against the save: {rc}")
    return rc


def train_argv(ckpt: Path, steps: int, every: int, *extra: str) -> list[str]:
    return [
        "-m", "repro.launch.train", "--arch", ARCH,
        "--batch", str(BATCH), "--seq", str(SEQ), "--steps", str(steps),
        "--ckpt-dir", str(ckpt), "--ckpt-every", str(every),
        "--codec", CODEC, "--log-every", "1", *extra,
    ]


# the digest kernel against the host oracle on the chip, at the CLI's chunk
# sizes: the tied embedding (bf16, 16-bit halves paired inside the kernel),
# an f32 AdamW moment, and an odd-length bf16 leaf with a half-filled word
DIGEST_CHECK = f"""
import json, time, jax, jax.numpy as jnp, numpy as np
from repro.kernels import ops, ref
from repro.runtime.env import device_report, enable_compile_cache
enable_compile_cache()
rng, cases = np.random.default_rng(0), []
for shape, dtype, cb in [(({VOCAB}, 896), jnp.bfloat16, 1 << 20),
                         (({VOCAB}, 896), jnp.float32, 4 << 20),
                         ((100_001,), jnp.bfloat16, 4096)]:
    x = jnp.asarray(rng.standard_normal(shape, dtype=np.float32), dtype)
    got = np.asarray(ops.chunk_digests(x, cb))
    times = []
    for _ in range(5):
        t = time.perf_counter(); ops.chunk_digests(x, cb).block_until_ready()
        times.append(time.perf_counter() - t)
    cases.append({{"shape": list(shape), "dtype": str(x.dtype), "chunk_bytes": cb,
                  "chunks": len(got), "warm_s": min(times),
                  "equal": bool(np.array_equal(got, ref.chunk_digests_np(np.asarray(x), cb)))}})
print("[digest] device", json.dumps({{**device_report(), "digest": ops.auto_dispatch()}}))
print("[digest] check", json.dumps(cases))
"""


def digest_check(deadline: float) -> None:
    out = run_phase("digest", ["-c", DIGEST_CHECK], deadline)
    check_device(one_json(out, "[digest] device"), 1)
    cases = one_json(out, "[digest] check")
    if len(cases) != 3 or not all(c["equal"] for c in cases):
        raise SmokeFailure(f"Pallas digests differ from the host oracle: {cases}")
    print("== digest: bit-identical to the host oracle; warm "
          + ", ".join(f"{c['dtype']} {c['shape']} {c['warm_s'] * 1e3:.2f} ms"
                      for c in cases), flush=True)


def probe(deadline: float) -> dict:
    """The devices JAX finds, asked of a child that exits at once."""
    code = (
        "import jax, json; d = jax.devices(); print(json.dumps({'platform': "
        "d[0].platform, 'kind': d[0].device_kind, 'count': len(d)}))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=max(1.0, deadline - time.monotonic()),
    )
    if out.returncode != 0:
        raise SmokeFailure(f"JAX did not start: {out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def one_chip(deadline: float) -> dict:
    digest_check(deadline)
    ckpt = WORK / "inline"
    # three saves: the checkpointer double-buffers its host shadow, so the
    # first save into each buffer copies everything and only the third
    # runs the on-device digest pass that finds changed chunks
    out = run_phase(
        "train", train_argv(ckpt, 6, 2, "--backend", "fork"), deadline
    )
    device = check_device(one_json(out, "[train] device"), 1)
    check_mesh(out, 1)
    first = losses(out)
    done = sorted(int(s) for s in re.findall(r"^\[ckpt-done\] step=(\d+)", out, re.M))
    if done != [2, 4, 6]:
        raise SmokeFailure(f"persisted checkpoints {done}, expected [2, 4, 6]")
    check_committed(ckpt, [4, 6])  # the policy keeps the last two
    summary = final_summary(out)
    if summary["timings"].get("shadow/digest", {}).get("count", 0) <= 0:
        raise SmokeFailure("no save ran the on-device digest pass")
    print(f"== train: steps {sorted(first)}, persisted {done}, committed "
          f"[4, 6]; peak {summary['peak_bytes_in_use']} B", flush=True)

    out = run_phase(
        "resume", train_argv(ckpt, 8, 2, "--backend", "fork"), deadline
    )
    check_device(one_json(out, "[train] device"), 1)
    rc = check_restore(out, 6)
    more = losses(out)
    if sorted(more) != [7, 8]:
        raise SmokeFailure(f"resume ran steps {sorted(more)}, expected [7, 8]")
    check_committed(ckpt, [8])
    print(f"== resume: start_step=6, {rc['chunks']} chunks bit-identical to "
          f"the save, checked in {rc['seconds']:.1f}s; peak "
          f"{final_summary(out)['peak_bytes_in_use']} B", flush=True)

    out = run_phase("serve", [
        "-m", "repro.launch.serve", "--arch", ARCH, "--ckpt-dir", str(ckpt),
        "--lazy", "--batch", "2", "--prompt-len", "16", "--gen", "8",
    ], deadline)
    check_device(one_json(out, "[serve] device"), 1, digest=False)
    if not re.search(r"^\[serve\] restored step 8 ", out, re.M):
        raise SmokeFailure("serve did not restore the step-8 image")
    m = re.search(r"^\[serve\] tokens (\[.*\]) finite_logits=True$", out, re.M)
    if m is None:
        raise SmokeFailure("serve printed no tokens, or non-finite logits")
    toks = json.loads(m.group(1))
    if len(toks) != 2 or any(len(r) != 8 or not all(0 <= t < VOCAB for t in r)
                             for r in toks):
        raise SmokeFailure(f"decoded tokens malformed: {toks}")
    print(f"== serve: decoded {toks} from the step-8 image", flush=True)

    pckpt = WORK / "proxy"
    out = run_phase("proxy", train_argv(
        pckpt, 2, 2, "--backend", "fork", "--device-runner", "proxy"
    ), deadline)
    check_device(one_json(out, "[train] proxy device"), 1, digest=False)
    app = one_json(out, "[train] app device")
    if app is not None and app.get("platform") == "tpu":
        raise SmokeFailure(f"the proxy-mode app took a TPU: {app}")
    losses(out)
    check_committed(pckpt, [2])
    print(f"== proxy: the proxy held the TPU, the app ran on {app}", flush=True)
    return device


def four_chips(deadline: float) -> dict:
    ckpt = WORK / "mesh4"
    out = run_phase("mesh4-train", train_argv(ckpt, 2, 2), deadline)
    device = check_device(one_json(out, "[train] device"), 4)
    check_mesh(out, 4)
    mesh = losses(out)
    check_committed(ckpt, [2])

    out = run_phase("mesh4-resume", train_argv(ckpt, 3, 2), deadline)
    check_device(one_json(out, "[train] device"), 4)
    check_mesh(out, 4)
    rc = check_restore(out, 2)
    mesh.update(losses(out))
    print(f"== mesh4: resumed at 2, {rc['chunks']} chunks bit-identical in "
          f"{rc['seconds']:.1f}s", flush=True)

    # the same steps in a process the TPU runtime gives one chip
    out = run_phase("one-device", train_argv(WORK / "one", 3, 100), deadline,
                    env=ONE_CHIP_ENV)
    check_device(one_json(out, "[train] device"), 1)
    check_mesh(out, 1)
    one = losses(out)
    if sorted(one) != sorted(mesh) or any(
        abs(mesh[s] - one[s]) > LOSS_RTOL * max(1.0, abs(one[s])) for s in one
    ):
        raise SmokeFailure(f"4-chip losses {mesh} != one-device losses {one}")
    print(f"== mesh4 vs one device: losses {mesh} vs {one}", flush=True)
    return device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    args = ap.parse_args(argv)
    deadline = time.monotonic() + DEADLINE_S
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT}/src", file=sys.stderr)
        return 2
    try:
        found = probe(deadline)
        print(f"== probe: {json.dumps(found)}", flush=True)
        if found.get("platform") != "tpu" or found.get("count", 0) < args.chips:
            raise SmokeFailure(f"needs {args.chips} TPU chip(s); JAX found {found}")
        shutil.rmtree(WORK, ignore_errors=True)
        device = (four_chips if args.chips == 4 else one_chip)(deadline)
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
