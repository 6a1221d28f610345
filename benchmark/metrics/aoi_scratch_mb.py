"""Device memory the single-chip buckets' scratch pools hold at the
window's end, in MiB: the program's gauge ``aoi.scratch_bytes`` (one
sample per AOI engine, summed)."""


def read(ctx):
    from goworld_tpu import telemetry

    v = [x for k, x in telemetry.snapshot().items()
         if k == "aoi.scratch_bytes" or k.startswith("aoi.scratch_bytes{")]
    if not v:
        return None
    return sum(v) / 2**20
