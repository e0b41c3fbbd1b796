"""Share of the HBM roofline that client EF + selection reaches: the least
time its bytes take at the chip's HBM bandwidth (``counts.select_bytes``:
read the delta and the residual, write the residual, write k values and
indices per block) over ``select_ms``. Its operations (3 an element) are
far under the bf16 peak, so the bytes bound it."""
import manifest


def read(r):
    ms = manifest.reader("select_ms")(r)
    if not ms or not r.peaks:
        return None
    return 100.0 * (r.select_bytes / r.peaks["hbm_bytes_per_s"]) / (ms / 1e3)
