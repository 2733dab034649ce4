"""Serving control: share of the prefill programs' token positions that
were padding (rows repeated to fill the program's ``slots`` rows, and bucket
padding), from the engine's admit counts over the window, %."""


def read(r):
    real, pad = r.counter("prefill_tokens"), r.counter("prefill_pad_tokens")
    return 100.0 * pad / (real + pad) if real + pad > 0 else None
