"""Host seconds of the program's first ``dispatch`` span: the first
step's call, which traces, lowers, compiles or loads from the cache, and
enqueues the step. Read from the program's own span table
(``repro.launch.tracing``) in this process; the table's set-up spans and
compile events go to standard error beside it."""
import json
import sys


def read(ctx):
    try:
        from repro.launch import tracing
    except ImportError:             # a program without the table
        return None
    summary = tracing.TABLE.summary()
    print("spans: " + json.dumps(summary), file=sys.stderr, flush=True)
    return tracing.TABLE.first("dispatch")
