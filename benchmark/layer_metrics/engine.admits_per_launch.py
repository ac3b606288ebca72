"""Admissions landed per admission program launched over the window
(counter ``engine.admissions_landed`` over counter
``engine.admit_launches``): how many waiting arrivals a launch admits in
one prefill program, 1.0 where every arrival is launched alone. Nothing
under a program without the series (one that launches an arrival a
program), or where nothing was launched."""
from counters import series_delta


def read(ctx):
    landed = series_delta(ctx, "engine.admissions_landed")
    launches = series_delta(ctx, "engine.admit_launches")
    if landed is None or not launches:
        return None
    return landed / launches
