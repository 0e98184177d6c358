"""Live health daemon: probes, SLOs and the HTTP exporter from one CLI.

Drives the framework's own :class:`telemetry.health.HealthMonitor` (device
HBM watermarks, bounded device probes, stream/worker liveness, resilience
signals, windowed SLOs) in the process it is started in. A process that is
not the one holding the chip sees host-side components only.

Modes:

* **watch** (default) — start the background monitor (and, with
  ``--port``, the ``/metrics`` + ``/healthz`` HTTP exporter) and print one
  rollup line per tick until interrupted::

      python tools/healthd.py --port 9100

* **--once** — single foreground poll, rollup JSON on stdout, exit code
  by state: 0 while serving, 2 once any component is FAILING. With
  ``--strict`` a DEGRADED component or any counted SLO breach also fails
  (exit 1) — the CI gate shape.
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def now_iso() -> str:
    return datetime.datetime.now(datetime.timezone.utc).isoformat(
        timespec="seconds"
    )


# -- CLI ---------------------------------------------------------------------


def _exit_code(rollup: dict, *, strict: bool) -> int:
    state = rollup.get("state", "OK")
    if state == "FAILING":
        return 2
    if strict:
        if state == "DEGRADED":
            return 1
        if (rollup.get("slo") or {}).get("total_breaches", 0):
            return 1
    return 0


def run_once(args) -> int:
    from spark_rapids_ml_tpu.telemetry import health

    mon = health.HealthMonitor(
        interval_s=args.interval,
        probe_mode=args.probe,
        probe_timeout_s=args.probe_timeout,
    )
    try:
        rollup = mon.poll_once()
    finally:
        mon.stop()
    print(json.dumps(rollup, indent=2))
    return _exit_code(rollup, strict=args.strict)


def run_watch(args) -> int:
    from spark_rapids_ml_tpu.telemetry import health, httpd

    mon = health.start_monitor(
        interval_s=args.interval,
        probe_mode=args.probe,
        probe_timeout_s=args.probe_timeout,
    )
    server = None
    if args.port is not None:
        server = httpd.start_http_server(args.port, with_monitor=False)
        print(f"[healthd] exporter at {server.url}", flush=True)
    print(
        f"[healthd] start {now_iso()} interval={mon.interval_s}s "
        f"probe={mon.probe_mode}",
        flush=True,
    )
    try:
        while True:
            # the monitor thread polls on its own cadence; this loop only
            # reports it
            time.sleep(mon.interval_s)
            rollup = mon.rollup() if mon.polls else mon.poll_once()
            states = " ".join(
                f"{c}={v['state']}" for c, v in rollup["components"].items()
            )
            print(
                f"[healthd] {now_iso()} state={rollup['state']} {states}",
                flush=True,
            )
    except KeyboardInterrupt:
        print("[healthd] interrupted", flush=True)
    finally:
        if server is not None:
            httpd.stop_http_server(stop_monitor=False)
        health.stop_monitor()
    return 0


def main(argv=None) -> int:
    from spark_rapids_ml_tpu.telemetry import health

    p = argparse.ArgumentParser(
        description="live health daemon: probes, SLOs, /metrics + /healthz"
    )
    p.add_argument(
        "--once", action="store_true",
        help="poll once, print the rollup JSON, exit by state",
    )
    p.add_argument(
        "--strict", action="store_true",
        help="with --once: DEGRADED or any SLO breach also fails (CI gate)",
    )
    p.add_argument(
        "--port", type=int, default=None,
        help="also serve /metrics,/healthz,/slo,/report on this port "
        "(0 = ephemeral; watch mode only)",
    )
    p.add_argument(
        "--interval", type=float, default=None,
        help=f"poll interval seconds (default {health.INTERVAL_VAR} or 5)",
    )
    p.add_argument(
        "--probe", choices=health.PROBE_MODES, default=None,
        help=f"device liveness probe mode (default {health.PROBE_VAR} or "
        "inline)",
    )
    p.add_argument(
        "--probe-timeout", type=float, default=None,
        help="probe deadline seconds (default "
        f"{health.PROBE_TIMEOUT_VAR} or 20)",
    )
    args = p.parse_args(argv)
    if args.once:
        return run_once(args)
    return run_watch(args)


if __name__ == "__main__":
    sys.exit(main())
