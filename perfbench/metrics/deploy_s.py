"""Seconds the job takes to deploy the pipeline: the newest ``deploy`` span
before the window (``StreamJob._deploy``: learner, bridge, trainer and its
``build_state``)."""
from perfbench import setup_records as sr


def read(ctx):
    deploys = sr.in_setup(ctx, "deploy")
    return deploys[-1].end - deploys[-1].start if deploys else None
