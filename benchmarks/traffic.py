"""The one traffic generator: a traffic file's parameters to the
client's statement order.

A traffic file (`traffic/<name>.json`) gives
  loop        "closed": the client sends its next statement when the
              last answer is fetched (the only loop so far);
  clients     1: one connection (the only number so far);
  statements  the cycle, as query names (`queries/<name>.sql`), sent in
              the file's order, cycle after cycle;
  trace_seconds  how long a `--trace 1` run keeps the profiler on.
A window ends on a whole cycle, so every run does whole cycles of the
same work and a rate does not swing with which statement was cut.
Nothing is drawn at random yet, so `--seed` changes the data only; the
PR that brings more clients or a drawn order brings them here."""

from __future__ import annotations

import itertools
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed" or mix.get("clients") != 1:
        raise ValueError(f"traffic {name}: only a closed loop of one client is generated so far")
    if not mix.get("statements"):
        raise ValueError(f"traffic {name}: needs statements")
    return mix


def cycles(mix: dict):
    """Endless cycles of statement names."""
    return itertools.repeat(list(mix["statements"]))
