#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the machine this is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Sets no JAX platform. Where JAX finds no TPU, or fewer chips than the cell
asks for, it names what it found on stderr, prints no result and exits 2.
``--rehearse-cpu`` is the one exception, made explicit: the same code at the
``rehearsal`` sizes of the configuration and traffic files, kernels through
the Pallas interpreter, the line marked a rehearsal and never a result.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: every number that decided ``correct``
beside its limit. The same numbers are the last lines of stderr.
"""
import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class Run:
    """What a driver is given: the cell, the seed, the window's length,
    whether to trace a slice, and the clock's reading at process start."""

    def __init__(self, cell, args, device, peaks):
        self.cell = cell
        self.seed = args.seed
        self.seconds = args.seconds
        self.trace = bool(args.trace)
        self.rehearsal = args.rehearse_cpu
        self.t0 = _T0
        self.root = ROOT
        self.device = device
        self.peaks = peaks
        self.keep_xplane = (args.keep_events + ".xplane.pb"
                            if args.keep_events else None)


def _metrics(run, outcome):
    from benchmark.harness import readers
    bench_units = {m["name"]: m["unit"] for m in
                   run.cell.bench["end_to_end"]}
    if not run.trace:
        return {name: {"value": outcome["end_to_end"][name],
                       "unit": bench_units[name]}
                for name in run.cell.end_to_end}
    out = {}
    for spec in run.cell.per_layer:
        value = readers.resolve(spec["reader"])(
            outcome["facts"], outcome["events"] or [], spec)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


def open_cell(args):
    """The cell, the device as JAX reports it and the chip's peaks; the
    cell is None, with the reason on stderr, where this machine cannot run
    it. Turns the compile cache on before the first compile."""
    from benchmark.harness import peaks
    from benchmark.harness.cell import Cell
    cell = Cell(args.workload)

    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if args.rehearse_cpu:
        if platform != "cpu":
            print(f"benchmark: --rehearse-cpu is for the CPU, JAX found "
                  f"{platform!r}", file=sys.stderr)
            return None, None, None
        chip_peaks = None
    elif platform != "tpu" or len(devices) < cell.chips:
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s) and "
              f"JAX found {len(devices)} device(s) of platform "
              f"{platform!r} ({kind}); nothing was run", file=sys.stderr)
        return None, None, None
    else:
        chip_peaks = peaks.peaks_for(kind)
    device = {"platform": platform, "kind": kind, "count": len(devices)}

    from paddle_tpu.kernels import _dispatch
    if args.rehearse_cpu:
        # what a TPU would resolve to, through the Pallas interpreter
        _dispatch.auto_impl = lambda: "interpret"
    else:
        from paddle_tpu.utils import compile_cache
        cell.compile_cache = compile_cache.enable()
        # A size cap makes the cache evict its oldest entry for each new
        # one. A cell that compiles more than the cap holds then loses, on
        # every run, each executable just before it asks for it again and
        # hits none (the serve cell under a 192 MiB cap: 0 hits in 110,
        # PERF.md Findings). One checkout's cache is a few hundred MiB.
        jax.config.update("jax_compilation_cache_max_size", -1)
    return cell, device, chip_peaks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="dry run on the CPU at the rehearsal sizes; the "
                         "line says rehearsal and is never a result")
    ap.add_argument("--keep-events", metavar="PATH",
                    help="with --trace 1, also write the traced slice's "
                         "event list there (how the tests' fixture was cut)")
    args = ap.parse_args(argv)

    from benchmark.harness import compare, trace_reduce
    cell, device, chip_peaks = open_cell(args)
    if cell is None:
        return 2
    run = Run(cell, args, device, chip_peaks)
    outcome = cell.driver.run(run)

    checks = outcome["checks"]
    device["memory_peak_bytes"] = outcome["memory_peak_bytes"]
    line = {"correct": compare.verdict(checks),
            "attempted": outcome["attempted"], "failed": outcome["failed"],
            "metrics": _metrics(run, outcome), "device": device}
    if run.trace:
        events = outcome["events"] or []
        device["busy_s"] = trace_reduce.busy_seconds(events)
        device["window_s"] = trace_reduce.window_seconds(events)
        line["breakdown"] = {
            "device_ops": trace_reduce.top_device_ops(events),
            "idle_gaps": trace_reduce.idle_gaps(events)}
        if args.keep_events:
            with open(args.keep_events, "w") as fh:
                json.dump(events, fh)
    if run.rehearsal:
        line["rehearsal_not_a_chip_run"] = True
    line["checks"] = {c["name"]: [c["value"], c["limit"]] for c in checks}
    sys.stdout.flush()
    cache = getattr(cell, "compile_cache", None)
    if cache is not None:
        outcome["notes"]["compile_cache"] = (
            f"{cache.hits} hits, {cache.misses} misses, "
            f"{cache.compile_seconds:.1f} s in the backend, "
            f"{cache.directory}")
    for key, value in outcome["notes"].items():
        print(f"note {key}: {value}", file=sys.stderr)
    for c in checks:
        print(f"check {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g})"
              f"{'' if c['value'] <= c['limit'] else '  <-- over'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
