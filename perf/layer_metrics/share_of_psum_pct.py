"""Collectives: the library's bus bandwidth over that of the reference
schedule (``psum``), same process, same buffers, host clock over whole
units. BASELINE.json's bar is 90."""


def read(ctx):
    ref = ctx.quantities.get("reference_busbw_GBps")
    if not ref:
        return None
    return 100.0 * ctx.quantities["busbw_GBps"] / ref
