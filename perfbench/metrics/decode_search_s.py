"""Seconds of `compile_decode()`'s strategy search: the program's own
`ff.compile.decode_search` span in the events of the telemetry session the
run holds open through set-up."""
import json

from perfbench.harness import program_spans

SPAN = "ff.compile.decode_search"


def read(facts):
    path = program_spans.session_file("events.jsonl")
    if path is None:
        return None
    with open(path) as f:
        events = [json.loads(line) for line in f if SPAN in line]
    durs = [e["dur"] for e in events
            if e.get("name") == SPAN and e.get("ph") == "X"]
    return sum(durs) if durs else None
