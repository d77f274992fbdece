"""Share of program steps dispatched behind a step still owed:
`serving/steps_dispatched{in_flight=1}` over both labels.  Source: program
counter."""
from benchmark.lib.step_record import pipeline_full_share


def compute(ctx):
    return pipeline_full_share(ctx["counters"])
