"""Share of the fleet step's device time that the least bytes it has to
move would take at the chip's peak HBM bandwidth: bandwidth bounds the
step, its operations are compares and moves."""
from harness import cost

PROGRAM = "jit_step"


def read(rd):
    ms = rd.program_ms(PROGRAM)
    if ms is None:
        return None
    sh = rd.shapes
    if not sh.get("w"):
        return None
    least_s = cost.step_bytes(sh["engine"], sh["m"], sh["k"], sh["w"]) \
        / cost.peaks(rd.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
