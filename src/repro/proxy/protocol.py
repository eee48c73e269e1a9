"""Device-proxy wire protocol (paper §3: application <-> proxy process).

CRUM's application process is "device-clean": it never owns device state;
every device API call is forwarded to the proxy. Here the control plane is
u32-length-prefixed msgpack frames over loopback TCP — the exact framing of
``repro.coord.protocol`` (``Connection``/``send_frame``/``recv_frame`` are
re-exported from there) — while the data plane is file-backed MAP_SHARED
mmap segments (``repro.proxy.segments``): step inputs/outputs never pickle
through the pipe, only tiny control frames do.

When tracing is enabled, REGISTER/STEP/SYNC/UPLOAD (and streamed CHUNKS)
frames may carry an optional ``ctx`` field — ``{"trace", "span",
"parent"}``, the causal trace context (repro.obs.trace) under which the
proxy-side service emits its execution span, so a merged trace links the
app's round tree to the proxy work it caused (repro.obs.critpath). The
field is absent when tracing is off; the untraced frames are
byte-identical.

Application -> proxy::

    PROGRAM   {spec}                 construct the step program (replayable)
    REGISTER  {layout, chunk_bytes,  attach the data plane; init state.
               transport?,           ``transport`` is ``"segment"`` (shared
               workdir?,             MAP_SHARED files, local zero-copy —
               device_capacity_bytes?, needs ``workdir``) or ``"stream"``
               page_bytes?,          (payloads travel as CHUNKS frames over
               eviction_policy?,     this connection — the remote form).
               promote_threshold?}   with a capacity the proxy hosts its
                                     device state in a ManagedSpace (UVM
                                     paging under a hard budget)
    UPLOAD    {paths, step, chunks?, ingest data-plane bytes into device
               n_frames?}            state. ``chunks`` ({path: [chunk
                                     indices]}) is the delta form: only
                                     those chunk ranges move — bytes on
                                     the wire scale with dirty chunks, not
                                     state size. Streamed transport: the
                                     payload follows as exactly
                                     ``n_frames`` CHUNKS frames
    CHUNKS    {codec, items, data}   one data-plane frame (streamed
                                     transport): ``items`` is a list of
                                     [path, chunk_index, raw_len] and
                                     ``data`` their concatenated bytes,
                                     optionally zstd-compressed per frame
    STEP      {step}                 run one train step — pipelined, NO reply
    FLUSH     {seq}                  pipeline barrier (control-plane only)
    SYNC      {epoch?}               device state -> data plane at this
                                     point in the pipeline. With ``epoch``
                                     the call is *pipelined like STEP*: no
                                     barrier, the app keeps issuing STEPs
                                     and matches the SYNCED{epoch} ack
                                     asynchronously. Without it: the
                                     legacy blocking barrier.
    SHUTDOWN  {}                     clean exit

Proxy -> application::

    OK        {op, ...}              ack for PROGRAM/REGISTER/UPLOAD
    ERR       {op, error}            the call failed; proxy stays up
    FLUSHED   {seq, step}            pipeline empty up to ``seq``
    CHUNKS    {codec, items, data}   streamed transport: dirty-chunk
                                     payload of the in-progress SYNC (sent
                                     before its SYNCED)
    SYNCED    {step, digest, metrics, chunks_synced, bytes_synced,
               epoch?, phase_us?, wire_bytes?, paging?}
                                     ``epoch`` echoes the SYNC's epoch;
                                     ``phase_us`` breaks the window down
                                     ({step, digest, sync} microseconds)
                                     for the pipeline observability path

STEP carrying no reply is the proxying economy the paper measures in
Fig. 4: the app runs ahead of the proxy exactly like JAX's async dispatch
runs ahead of the device (see ``core/drain.py``); SYNC is the flush. An
epoch-tagged SYNC extends the same economy to the sync boundary itself:
the proxy still executes it in pipeline order (so the image is exactly
the step-boundary state), but the app overlaps the drain+digest+fetch
work with its next steps instead of stalling on the ack.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.coord.protocol import (  # noqa: F401  (re-exported framing)
    Connection,
    connect,
    recv_frame,
    send_frame,
)

MSG_PROGRAM = "PROGRAM"
MSG_REGISTER = "REGISTER"
MSG_UPLOAD = "UPLOAD"
MSG_CHUNKS = "CHUNKS"
MSG_STEP = "STEP"
MSG_FLUSH = "FLUSH"
MSG_SYNC = "SYNC"
MSG_SHUTDOWN = "SHUTDOWN"

MSG_OK = "OK"
MSG_ERR = "ERR"
MSG_FLUSHED = "FLUSHED"
MSG_SYNCED = "SYNCED"


class ProxyDiedError(RuntimeError):
    """The proxy process is gone (EOF/broken pipe/timeout past liveness)."""


@dataclass
class ProxyServiceConfig:
    """Everything a fresh proxy incarnation needs to come up and connect.

    Deliberately minimal: program/layout/data arrive as *replayed API
    calls* over the connection, never as spawn arguments — that is what
    makes a respawned proxy reconstructible from the API log alone.
    """

    host: str
    port: int
    sock_timeout_s: float = 1.0
    # observability (not part of the replayable state — a respawn works
    # with or without it): where to write this incarnation's trace shard.
    # Normally inherited via CRUM_OBS_DIR; explicit here for spawn paths
    # whose environment is scrubbed.
    obs_dir: str | None = None
    obs_run: str | None = None
