"""Times the train step's body was traced during set-up: the program's
trace-time counter `ff_program_traces_total` for `train_step` and
`train_scan`, as the telemetry session held it when it closed at the
window's open. One build is the least; each more is a compile or a cache
load that set-up paid for."""
import json

from perfbench.harness import program_spans

COUNTER, PROGRAMS = "ff_program_traces_total", ("train_step", "train_scan")


def read(facts):
    path = program_spans.session_file("metrics.jsonl")
    if path is None:
        return None
    last = {}  # a session may write several snapshots: the last one holds
    with open(path) as f:
        for line in f:
            if COUNTER in line:
                r = json.loads(line)
                if r.get("name") == COUNTER:
                    last[r["labels"].get("program")] = r["value"]
    if not any(p in last for p in PROGRAMS):
        return None
    return sum(last.get(p, 0) for p in PROGRAMS)
