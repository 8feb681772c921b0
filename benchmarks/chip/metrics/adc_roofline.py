"""The ADC stage's share of its roofline, in %: the least time the chip
could take for the algorithm's work (the larger of operations over the
compute peak and bytes over the HBM bandwidth; the bytes bound it at every
size this benchmark runs) over the stage's device time in the traced
window. The stage is the programs named in STAGE."""
import os

from harness.spec import HERE, load_module

# the jitted entry points of kernels/ivf_adc.py, as the trace names them
STAGE = ("jit_ivf_adc", "jit_ivf_adc_blocked", "jit_ivf_adc_run_resident")


def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    seconds = t.module_s(STAGE)
    if seconds <= 0:
        raise ValueError(f"traced window has no device time in {STAGE}: "
                         f"programs seen {t.top_modules(20)}")
    work = load_module(os.path.join(HERE, "roofline", "adc.py")).count(run)
    if work is None:
        return None
    peak = run.peaks[run.device_kind]
    least = max(work["ops"] / peak["bf16_flop_per_s"],
                work["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
