"""On-device densify: share of its roofline, in %, for feeds that densify on
the chip (``ell_device``).

The operation is bound by memory: it must read the ELL slab (float32 values
and int32 columns, rows x K) and write the dense float32 batch (rows x
genes), and does no arithmetic beyond placing values.  The least time is
those bytes over the chip's HBM bandwidth; the time taken is the device time
of the ``jit_densify`` module, whatever implements it inside."""
from bench import peaks


def densify_bytes(rows: int, k: int, n_genes: int) -> int:
    return rows * k * (4 + 4) + rows * n_genes * 4


def read(r):
    shapes = r.feed_shapes
    times = r.trace.module_times("jit_densify")
    if not shapes or "rows" not in shapes or not times:
        return None
    least = densify_bytes(shapes["rows"], shapes["k"], shapes["n_genes"]) / peaks.peak(
        r.device_kind, "hbm_bytes_per_s")
    return 100.0 * least / (sum(times) / len(times))
