"""Share of the ADC grid's steps in the window that visit the shared
all-pad block, in %: 100 x (1 - real steps / steps), summed over the
``ivf.adc`` spans (steps = plan-bucket rows x visit-table width; real
steps = the pairs ``visit_sharing`` counts off the pad block). Batches
whose visit table never came to the host carry no count."""
from harness.spans import window_records


def read(run):
    recs = window_records(run)
    if recs is None:
        return None
    adc = [r.attrs for r in recs if r.name == "ivf.adc"]
    if not adc:
        raise ValueError("no ivf.adc span in the window")
    steps = sum(a["steps"] for a in adc)
    if not steps:
        return None
    return 100.0 * (1.0 - sum(a["real_steps"] for a in adc) / steps)
