"""device_transfer_s: seconds of host-to-device copies of row-proportional arrays during the set-up (the binned matrix, the objective's labels and weights, initial scores, a validation set's): the sum of the setup/transfer spans, each closed when its copy is ready.  A program without such spans (older than PR 37) reads a measured 0."""

from benchmark import setup_spans


def read(facts):
    return setup_spans.span_seconds(facts, setup_spans.TRANSFER)
