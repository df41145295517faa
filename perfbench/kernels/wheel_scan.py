"""engine/kernels_pallas.wheel_scan_pallas: one gridless call scatters
N lanes into nb buckets (occupancy count, per-bucket minimum) and scans
for the first occupied one.  The calendar wheel calls it at nb = 768
(three class wheels of 256, once per batch) and nb = 256 (the stop
wheel, once per ladder level).  HBM traffic per call: three int32
lane planes in ([ceil(N/128), 128] bucket ids, key high and low
words), the count, high-word and low-word minimum columns ([nb, 1]
int32 each) and the [8, 1] nearest column out.  Its bound is VPU
compares, not bytes: each lane row is compared with every bucket
twice, 2 * nb * rows * 128 compares a call."""

LANES = 128
BUCKETS = (768, 256)


def rows(cfg: dict) -> int:
    return -(-int(cfg["clients"]) // LANES)


def trace_pattern(cfg: dict, nb: int = BUCKETS[0]) -> str:
    """The kernel's op in a trace: the custom call named wheel_scan
    whose first result is the [nb, 1] count column."""
    return rf"^%wheel_scan(\.\d+)? = \(s32\[{nb},1\]"


def bytes_per_call(cfg: dict, nb: int = BUCKETS[0]) -> int:
    return 4 * (3 * rows(cfg) * LANES + 3 * nb + 8)


def compares_per_call(cfg: dict, nb: int = BUCKETS[0]) -> int:
    return 2 * nb * rows(cfg) * LANES
