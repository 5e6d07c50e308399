"""Bytes the restored KV needs at its stored precision, from each load
op's tokens x layers, over the wall time of the window's load ops, in
GB/s.  The stored layout is the family's (``kv_bytes_per_token_layer``,
and ``kv_scale_bytes_per_chunk`` for int8).  Bytes the datapath stages
beyond that (other layers' rows, padding) are not counted, so wire waste
shows as a lower rate."""
from harness.arch import family_of


def read(ctx):
    a = ctx.arch
    fam = family_of(a)
    itemsize = 1 if ctx.store["quant"] == "int8" else 2
    nbytes, secs = 0, 0.0
    for s in ctx.rec.ops:
        if s.kind == "load" and not s.from_reference:
            t0, t1 = s.tokens
            layers = s.layers[1] - s.layers[0]
            nbytes += (t1 - t0) * layers * fam.kv_bytes_per_token_layer(
                a, itemsize)
            if itemsize == 1:
                nbytes += -(-(t1 - t0) // a.chunk_size) \
                    * fam.kv_scale_bytes_per_chunk(a)
            secs += s.t1 - s.t0
    return nbytes / secs / 1e9 if secs else None
