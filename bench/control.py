"""Readings that set a cell's limits: the program's, and the control's.

    python bench/control.py --workload <cell> --seeds 12 --control-seeds 3 \
        --seconds 2 --first-seed N

In one process (set-up is paid once), runs the cell on ``--seeds`` seeds as
the benchmark does, then on ``--control-seeds`` further seeds with the
control in the program's place: the plain reference computed with its
input, output and intermediate spectra rounded to bfloat16, the step below
the float32 the configurations state. Prints one JSON line per run with the
numbers compared, then the lower reading (largest over the program's runs)
and the upper reading (smallest over the control's) of each number. The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _bf16(a):
    import jax.numpy as jnp

    if jnp.iscomplexobj(a):
        return jnp.real(a).astype(jnp.bfloat16).astype(jnp.float32) + 1j * (
            jnp.imag(a).astype(jnp.bfloat16).astype(jnp.float32))
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _rfft2_bf16(x):
    import jax.numpy as jnp

    return _bf16(jnp.fft.rfft2(_bf16(x))).astype(jnp.complex64)


@functools.cache
def _jitted(fn):
    import jax

    return jax.jit(fn)


def rfft2_control(x):
    """The reference real 2D FFT (XLA's own FFT, not the program's engines)
    with its input and output held in bfloat16; one jitted program, so that
    it fits beside the cell's stack."""
    return _jitted(_rfft2_bf16)(x)


#: The control of each op: what stands in for the program's call.
CONTROLS = {"rfft2": rfft2_control}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=2**31 + 1000)
    args = ap.parse_args(argv)

    from bench import harness

    spec = harness.load_spec()
    cell = harness.find_cell(spec, args.workload)
    control = CONTROLS[harness.load_traffic(cell["traffic"])["op"]]
    readings = {"program": {}, "control": {}}
    runs = [("program", None)] * args.seeds + [("control", control)] * args.control_seeds
    for i, (side, override) in enumerate(runs):
        seed = args.first_seed + i
        res = harness.run_cell(args.workload, seed, args.seconds, False, spec=spec,
                               override=override)
        print(json.dumps({"side": side, "seed": seed, "checks": res["checks"],
                          "correct": res["correct"], "metrics": res["metrics"]}),
              flush=True)
        for k, c in res["checks"].items():
            readings[side].setdefault(k, []).append(c["value"])
    summary = {k: {"lower": max(v), "upper": min(readings["control"].get(k, [math.nan])),
                   "program": v, "control": readings["control"].get(k)}
               for k, v in readings["program"].items()}
    print(json.dumps({"workload": args.workload, "readings": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
