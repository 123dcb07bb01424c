"""Between-program device idle that lies inside feed.wait (the loop blocked on an
empty feed queue), per whole step: the device waited for input.
From the program's recorder through benchmark/spans.py; silent without it."""
from benchmark import spans


def read(run):
    return spans.feed_starved_ms(run)
