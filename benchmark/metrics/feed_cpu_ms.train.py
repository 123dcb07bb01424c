"""CPU time of the feed thread inside feed.source, feed.transform, feed.h2d and
feed.put_wait, per batch of the traced window: the same floor for the input side.
From the program's recorder through benchmark/spans.py; silent without it."""
from benchmark import spans


def read(run):
    return spans.feed_cpu_ms(run)
