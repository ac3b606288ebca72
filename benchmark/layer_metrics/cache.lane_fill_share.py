"""Share of the serving cache's device memory that holds values (%): the
program's gauge ``cache.bytes`` (the allocated buffers' logical bytes: what
their shapes and types say) over ``cache.device_bytes`` (what the same
buffers occupy ON the device, tile padding included). 100 where every
cached row fills its lanes (rows 128 values wide); 50 where a 64-wide
bfloat16 row is padded to a 128-lane tile: the number a layout or a kernel
for heads narrower than a tile moves. A program without either gauge (or a
runtime that does not say what a buffer occupies) gives nothing."""


def read(ctx):
    metrics = ctx["after"]["status"]["metrics"]
    held = metrics.get("cache.bytes")
    device = metrics.get("cache.device_bytes")
    if not held or not device or not held.get("value") or not device.get(
            "value"):
        return None
    return 100.0 * held["value"] / device["value"]
