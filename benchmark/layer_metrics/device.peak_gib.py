"""Peak bytes in use on the fullest device (``GET /`` ``device``): what
slots x window fits into."""


def read(ctx):
    peaks = [d["peak_bytes_in_use"]
             for d in ctx["after"]["status"]["device"]["devices"]
             if d["peak_bytes_in_use"] is not None]
    return max(peaks) / 2**30 if peaks else None
