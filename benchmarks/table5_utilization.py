"""Paper Tables 4/5 analogue: device-utilization comparison.

FPGA slice/LUT/DSP counts have no TPU meaning; the resources that play
"area"'s role here (DESIGN.md §2) are:

  * compiled code size + HLO instruction count  (spatial footprint)
  * peak temp bytes (memory_analysis)           (register/RAM footprint)
  * modeled HBM traffic of the Pallas kernels   (fused = 1 round trip vs
    staged = log2 N) — the paper's α reappears as the traffic ratio.

Proposed (looped / fused-kernel) vs traditional (unrolled / staged-kernel).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import repro.xfft as xfft
from benchmarks.common import emit
from repro.kernels.ops import hbm_traffic_model


def _compiled_stats(variant: str, n: int, batch: int = 64):
    x = jax.ShapeDtypeStruct((batch, n), jnp.complex64)
    with xfft.config(variant=variant):
        compiled = jax.jit(xfft.fft).lower(x).compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    n_instr = sum(
        1 for l in hlo.splitlines() if "=" in l and not l.strip().startswith("//")
    )
    return {
        "code_bytes": mem.generated_code_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "hlo_instructions": n_instr,
    }


def run():
    print("# Table 5 analogue: compiled-artifact utilization, looped vs unrolled")
    for n in (64, 1024, 4096):
        loop = _compiled_stats("looped", n)
        unroll = _compiled_stats("unrolled", n)
        emit(
            f"table5_codesize_N{n}",
            0.0,
            f"hlo_instr {loop['hlo_instructions']} vs {unroll['hlo_instructions']}; "
            f"temp {loop['temp_bytes']} vs {unroll['temp_bytes']} B",
        )
    print("# Pallas-kernel HBM traffic (fused 'reuse' kernel vs staged baseline)")
    for n in (256, 1024, 4096):
        fused = hbm_traffic_model(128, n, fused=True)
        staged = hbm_traffic_model(128, n, fused=False)
        emit(
            f"table5_hbm_traffic_N{n}",
            0.0,
            f"fused {fused} B vs staged {staged} B; ratio={fused/staged:.4f} "
            f"(paper alpha={1/jnp.log2(n):.4f})",
        )


if __name__ == "__main__":
    run()
