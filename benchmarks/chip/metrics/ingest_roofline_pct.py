"""Share of the HBM roofline that the fused ingest and FedAMS step
reaches: the least time its bytes take at the chip's HBM bandwidth
(``counts.ingest_bytes``: read and write x, m, v and v-hat in float32,
read every client's k values and indices per block) over ``ingest_ms``.
Its few operations an element are far under the bf16 peak, so the bytes
bound it."""
import manifest


def read(r):
    ms = manifest.reader("ingest_ms")(r)
    if not ms or not r.peaks:
        return None
    return 100.0 * (r.ingest_bytes / r.peaks["hbm_bytes_per_s"]) / (ms / 1e3)
