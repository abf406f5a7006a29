"""Share of traces whose every due assignment the last round returned,
against the generator's truth (``checks/callgraph_truth.py``)."""


def read(m):
    return m["grade_last"]["trace_accuracy_pct"]
