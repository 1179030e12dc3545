"""``edl_train_compile_cache_events_total`` of kind miss over the whole run: 0
on every run but a checkout's first."""

NAME = "cache_misses"
UNIT = "count"
LAYER = "Compile / cache"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(run):
    series = run.at_end["registry"].get("edl_train_compile_cache_events_total")
    if series is None:
        return None
    return series.get('{kind="miss"}', 0.0)
