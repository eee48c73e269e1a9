"""Serving driver: prefill + batched decode with CRUM lazy restore.

Demonstrates the paper's read-fault economics on the restore path: with
``--lazy``, parameters materialize on first use with exponential
read-ahead, so time-to-first-token beats a full eager restore.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --ckpt-dir /tmp/ckpt --prompt-len 32 --gen 16

With ``--device-runner proxy`` decode executes in a device-proxy process
via the ``decode_arch`` step program — and with ``--proxy-endpoint`` that
proxy is a *remote* one, served by a ``repro.remote.host`` daemon over the
streamed chunk transport: the restored params ride the wire once (lazy
restore feeds the push leaf by leaf), then every SYNC moves only the
chunks decode dirtied (cache/toks), never the clean params.

    PYTHONPATH=src python -m repro.remote.host --port 7070   # machine B
    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --ckpt-dir /tmp/ckpt --lazy --device-runner proxy \
        --proxy-endpoint 127.0.0.1:7070                      # machine A
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_archs
from repro.core import RestoreManager
from repro.checkpoint import ChunkStore
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.runtime.env import device_report, enable_compile_cache, keep_off_device
from repro.utils.tree import flatten_with_paths


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None, help="restore params from here")
    ap.add_argument("--lazy", action="store_true", help="lazy restore w/ read-ahead")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--device-runner", choices=["inline", "proxy"],
                    default="inline",
                    help="proxy: decode in a device-proxy process "
                         "(decode_arch step program)")
    ap.add_argument("--proxy-endpoint", default=None, metavar="HOST:PORT",
                    help="connect to a remote proxy-host daemon instead of "
                         "spawning a local proxy (implies the streamed "
                         "transport)")
    ap.add_argument("--transport", choices=["segment", "stream"], default=None,
                    help="proxy data plane (default: stream when "
                         "--proxy-endpoint is given, else segment)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.device_runner == "proxy":
        return _serve_proxy(args)

    cfg = get_config(args.arch, smoke=args.smoke)
    model = build(cfg)
    mesh = make_host_mesh((jax.device_count(),), ("data",))
    print(f"[serve] device {json.dumps(device_report())}", flush=True)
    # one compiled program each for prefill and decode, not op-by-op
    # dispatch of every layer
    prefill = jax.jit(model.prefill, static_argnums=2) if model.prefill else None
    decode = jax.jit(model.decode)

    with jax.set_mesh(mesh):
        t0 = time.perf_counter()
        if args.ckpt_dir:
            rm = RestoreManager(ChunkStore(args.ckpt_dir))
            if args.lazy:
                lazy, manifest = rm.restore(lazy=True)
                # materialize exactly the params subtree, leaf by leaf
                flat = {
                    p[len("device/params/"):]: lazy[p]
                    for p in lazy.keys()
                    if p.startswith("device/params/")
                }
                params_shape = jax.eval_shape(lambda: model.init(jax.random.key(0)))
                flat_shape, treedef = flatten_with_paths(params_shape)
                from repro.utils.tree import unflatten_from_paths

                params = unflatten_from_paths(
                    treedef, {k: jnp.asarray(v) for k, v in flat.items()}
                )
                lazy.close()
            else:
                state, manifest = rm.restore()
                params = jax.tree.map(jnp.asarray, state["device"]["params"])
            print(f"[serve] restored step {manifest.step} in "
                  f"{time.perf_counter()-t0:.3f}s (lazy={args.lazy})")
        else:
            params = model.init(jax.random.key(0))
            print(f"[serve] fresh init in {time.perf_counter()-t0:.3f}s")

        B, P, G = args.batch, args.prompt_len, args.gen
        cache_len = P + G
        rng = np.random.default_rng(0)
        if cfg.frontend == "audio":
            prompt = jnp.asarray(
                rng.integers(0, cfg.vocab_size, (B, P, cfg.audio_codebooks)), jnp.int32
            )
            batch = {"inputs": prompt}
        elif cfg.frontend == "vision":
            batch = {
                "patches": jnp.asarray(
                    rng.standard_normal((B, cfg.num_patches, cfg.d_model)),
                    jnp.bfloat16,
                ),
                "inputs": jnp.asarray(
                    rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32
                ),
            }
        else:
            batch = {
                "inputs": jnp.asarray(
                    rng.integers(0, cfg.vocab_size, (B, P)), jnp.int32
                )
            }

        t1 = time.perf_counter()
        if prefill is not None:
            logits, cache = prefill(params, batch, cache_len)
        else:
            # SSM/hybrid: prefill by decoding the prompt token-by-token
            cache = model.init_cache(B, cache_len)
            for t in range(P):
                tok = batch["inputs"][:, t]
                logits, cache = decode(params, cache, tok)
        jax.block_until_ready(logits)
        ttft = time.perf_counter() - t1
        print(f"[serve] prefill({P} tokens) -> first logits in {ttft:.3f}s")

        def sample(lg):
            return jnp.argmax(lg, axis=-1).astype(jnp.int32)

        toks = sample(logits if logits.ndim == 2 else logits[:, -1])
        t2 = time.perf_counter()
        out = [toks]
        for _ in range(G - 1):
            logits, cache = decode(params, cache, toks)
            toks = sample(logits)
            out.append(toks)
        jax.block_until_ready(toks)
        dt = time.perf_counter() - t2
        print(f"[serve] generated {G-1} steps in {dt:.3f}s "
              f"({(G-1)*B/max(dt,1e-9):.1f} tok/s)")
        tokens = np.stack([np.asarray(t).reshape(B, -1)[:, 0] for t in out], 1)
        print(f"[serve] tokens {json.dumps(tokens.tolist())} "
              f"finite_logits={bool(jnp.isfinite(logits).all())}", flush=True)
    return 0


def _restored_params(args):
    """Restore the params subtree (eagerly, or leaf-by-lazy-leaf)."""
    rm = RestoreManager(ChunkStore(args.ckpt_dir))
    t0 = time.perf_counter()
    if args.lazy:
        lazy, manifest = rm.restore(lazy=True)
        flat = {
            p[len("device/params/"):]: np.asarray(lazy[p])
            for p in lazy.keys()
            if p.startswith("device/params/")
        }
        lazy.close()
    else:
        state, manifest = rm.restore()
        flat, _ = flatten_with_paths(state["device"]["params"])
        flat = {p: np.asarray(v) for p, v in flat.items()}
    print(f"[serve] restored step {manifest.step} in "
          f"{time.perf_counter()-t0:.3f}s (lazy={args.lazy})")
    return flat


def _serve_proxy(args) -> int:
    """Decode through a (possibly remote) device proxy; this process
    stays on the host CPU."""
    keep_off_device()
    from repro.proxy import ProxyRunner, make_program
    from repro.remote.transport import endpoint_arg
    from repro.utils.tree import unflatten_from_paths

    spec = {
        "name": "decode_arch", "arch": args.arch, "smoke": bool(args.smoke),
        "batch": args.batch, "prompt_len": args.prompt_len, "gen": args.gen,
    }
    provider = None
    if args.proxy_endpoint:
        ep = endpoint_arg(args.proxy_endpoint)
        provider = lambda failed=False: ep  # noqa: E731 — static placement
    transport = args.transport or ("stream" if args.proxy_endpoint else "segment")
    prog = make_program(spec)
    init = prog.init_state()
    if args.ckpt_dir:
        flat_params = _restored_params(args)
        have, treedef = flatten_with_paths(init["params"])
        missing = set(have) - set(flat_params)
        if missing:
            raise SystemExit(
                f"checkpoint lacks params for {sorted(missing)[:3]}..."
            )
        init["params"] = unflatten_from_paths(
            treedef, {p: flat_params[p] for p in have}
        )

    runner = ProxyRunner(
        spec, transport=transport, endpoint_provider=provider,
        chunk_bytes=1 << 20,
    )
    t0 = time.perf_counter()
    runner.start(device_state=init)
    push_s = time.perf_counter() - t0
    where = args.proxy_endpoint or "local"
    print(f"[serve] proxy={where} transport={transport} "
          f"state pushed in {push_s:.3f}s", flush=True)
    try:
        total = args.prompt_len + args.gen
        t1 = time.perf_counter()
        for n in range(1, total):
            runner.step(n)
        state, info = runner.sync_state()
        dt = time.perf_counter() - t1
        toks = np.asarray(state["toks"])[:, args.prompt_len:]
        print(f"[serve] decoded {total - 1} steps in {dt:.3f}s "
              f"({(total - 1) * args.batch / max(dt, 1e-9):.1f} tok/s, "
              f"restarts={runner.restarts})")
        tstats = info.get("transport", {})
        print(f"[serve] sync wire: chunks={info.get('chunks_synced')} "
              f"bytes={info.get('bytes_synced')} "
              f"wire_rx={tstats.get('wire_rx')} (params stay clean)")
        print(f"[serve] sample tokens: {toks[:, 0].tolist()}")
    finally:
        runner.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
