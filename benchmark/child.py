#!/usr/bin/env python3
"""child.py: the one process of a run that holds the chip.

    python benchmark/child.py <control-dir> <chain_server arguments...>

Calls `gethsharding_tpu.rpc.chain_server.main` unchanged, so what is
measured is the server a user starts. Beside it runs one side thread
that does what only the chip's holder can do, on request of the parent
(`run.py`), which asks by creating a file in the control directory:

  trace.start -> jax.profiler.start_trace; answers with trace.started
  trace.stop  -> stop_trace, reduce the .xplane.pb (`reduce_trace.py`),
                 answer with trace.json
  mem.req     -> answer with mem.json: peak bytes in use on the fullest
                 device, as `memory_stats()` reports them

Every answer is written under another name and renamed, so the parent
never reads half a file. `chain_server` has no switch for a device
trace; when it gets one, this thread goes (PERF.md, Open questions).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
POLL_S = 0.05


def _answer(control: str, name: str, body: dict) -> None:
    tmp = os.path.join(control, name + ".part")
    with open(tmp, "w") as out:
        json.dump(body, out)
    os.replace(tmp, os.path.join(control, name))


def _take(control: str, name: str) -> bool:
    """True once per request file: the file is removed as it is taken."""
    try:
        os.remove(os.path.join(control, name))
    except FileNotFoundError:
        return False
    return True


def _memory_peak() -> dict:
    import jax

    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dev in jax.local_devices()]
    return {"memory_peak_bytes": max(peaks), "per_device": peaks}


def _stop_and_reduce(trace_dir: str) -> dict:
    import jax

    sys.path.insert(0, HERE)
    import reduce_trace

    def note(msg):
        print(f"[child] trace: {msg}", file=sys.stderr, flush=True)

    t0 = time.monotonic()
    jax.profiler.stop_trace()
    stopped = time.monotonic()
    note(f"stop_trace took {stopped - t0:.1f} s")
    per_device, host = reduce_trace.load(trace_dir)
    loaded = time.monotonic()
    note(f"loaded {[len(e) for e in per_device]} device and {len(host)} "
         f"host events in {loaded - stopped:.1f} s")
    body = reduce_trace.reduce_events(per_device, host)
    body["stop_s"] = stopped - t0
    body["reduce_s"] = time.monotonic() - stopped
    note(f"reduced in {time.monotonic() - loaded:.1f} s")
    return body


def serve_control(control: str) -> None:
    """The side thread: answer the parent's requests until the process
    ends. A request that raises answers with the error, so the parent
    fails with a reason and not with a timeout."""
    trace_dir = os.path.join(control, "trace")
    while True:
        time.sleep(POLL_S)
        try:
            if _take(control, "mem.req"):
                _answer(control, "mem.json", _memory_peak())
            if _take(control, "trace.start"):
                import jax

                options = jax.profiler.ProfileOptions()
                # the Python tracer records every call of the marshal's
                # loops: megabytes a second, and it slows what it traces
                options.python_tracer_level = 0
                options.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=options)
                _answer(control, "trace.started", {})
            if _take(control, "trace.stop"):
                _answer(control, "trace.json", _stop_and_reduce(trace_dir))
        except Exception as exc:  # noqa: BLE001 - reported to the parent
            _answer(control, "error.json", {"error": repr(exc)})


def main(argv) -> int:
    control, server_args = argv[0], argv[1:]
    threading.Thread(target=serve_control, args=(control,),
                     name="benchmark-control", daemon=True).start()
    from gethsharding_tpu.rpc.chain_server import main as chain_server

    return chain_server(server_args)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
