"""Read-phase kernels: their share of the HBM roofline.  The bytes one
wave's read phase needs (``chipbench.work.read_phase_bytes``), times the
waves traced, over the device time of the read-phase Mosaic kernels times
the chip's peak HBM bandwidth.  The read phase moves a few hundred KB per
wave and computes little, so bandwidth bounds it."""
import re

from chipbench import work
from chipbench.peaks import peaks

# the read-phase kernels, as the trace names their operations
# ("%version_scan.24 = (s32[1024,128]...) custom-call(...)")
KERNELS = ("version_scan", "potential_matrix", "wave_commit")
OP = re.compile(r"%(\w+)\.\d+ = \(?\w+\[(\d+)")


def is_read_phase(name: str, T: int) -> bool:
    """A read-phase kernel operation: ``potential_matrix`` or
    ``wave_commit``, or a ``version_scan`` over a whole wave's ops (at
    least ``T`` rows); the commit loop's per-transaction ``version_scan``
    calls scan ``O`` rows and are not part of the read phase."""
    m = OP.match(name)
    if m is None or m.group(1) not in KERNELS:
        return False
    return m.group(1) != "version_scan" or int(m.group(2)) >= T


def read(ctx):
    t = ctx.trace
    if t is None or not t.waves:
        return None
    cfg = ctx.cfg
    s = t.op_s(lambda n: is_read_phase(n, cfg["T"]))
    if s <= 0:
        return None
    need = work.read_phase_bytes(cfg["T"], cfg["O"], cfg["n_versions"])
    bw = peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need * t.waves / (s * bw)
