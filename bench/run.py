"""Run one cell of the chip benchmark and print its result line.

    python bench/run.py --workload protein-train --seed 7 --seconds 30 --trace 0

One process per call: it loads and warms up the cell (``setup_s``),
measures for ``--seconds``, checks what the timed path produced against
the plain reference under ``bench/reference/``, and prints one JSON
object as the last line of standard output. Progress, compile seconds,
per-epoch times and each compared number with its limit go to standard
error. It exits non-zero, with no result line, when JAX finds no
TPU, fewer chips than the cell asks for, or a chip that
``bench/peaks.json`` lacks.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"


def enable_cache() -> None:
    """JAX's persistent compile cache at a fixed path inside the checkout,
    for every program however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        spec = harness.load_spec(ROOT)
        cell = harness.find_cell(spec, args.workload)
        import jax
        devices = jax.devices()
        peak = harness.check_devices(devices, cell["chips"],
                                     harness.load_peaks())
    except (harness.BenchError, FileNotFoundError, RuntimeError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    enable_cache()
    harness.log(f"device platform={devices[0].platform} "
                f"kind={devices[0].device_kind} count={len(devices)} "
                f"jax={jax.__version__} cache={CACHE_DIR}")
    result = harness.run_cell(spec, cell, seed=args.seed,
                              seconds=args.seconds, trace=bool(args.trace),
                              devices=devices, peak=peak, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
