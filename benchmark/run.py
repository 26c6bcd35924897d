#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the accelerator this
machine holds.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (JAX start, data made from the seed, the build through the
library's public constructors, one warm-up operation) is timed as
``setup_s``.  Then operations run back to back for ``--seconds``.  With
``--trace 0`` the last line carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the last line
carries the per-layer metrics, the device's busy and window seconds and
a breakdown.  Once the window has closed, the outputs are compared with
a plain reference; each compared number is printed beside its limit.

No TPU, or fewer chips than the cell asks for: exit 2, no result.
The library must be the ``legate_sparse_tpu`` of this checkout: exit 3
otherwise.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(workload: str):
    """(cell, devices) of ``workload`` on this machine's TPU, with the
    compile cache placed; raises SystemExit with a non-zero code where
    the chips or the library are missing."""
    sys.path.insert(0, ROOT)
    from benchmark import harness

    cell = harness.load_cell(workload)

    # The compile cache lives at a fixed path inside the checkout, so
    # only a cell's first run here compiles.  Every program is cached,
    # however short its compile.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        sys.stderr.write(
            f"{workload} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s)\n")
        raise SystemExit(2)
    try:
        import legate_sparse_tpu
    except ImportError as e:
        sys.stderr.write(f"the library is missing: {e}\n")
        raise SystemExit(3)
    if not os.path.abspath(legate_sparse_tpu.__file__).startswith(
            ROOT + os.sep):
        sys.stderr.write("legate_sparse_tpu was imported from "
                         f"{legate_sparse_tpu.__file__}, outside {ROOT}\n")
        raise SystemExit(3)
    return cell, devices[:cell.chips]


def main(argv=None) -> int:
    args = _args(argv)
    cell, devices = prepare(args.workload)
    from benchmark import harness

    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, t_start=T_START)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
