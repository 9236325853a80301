"""Milliseconds of ``SaddleNuSVC.fit``'s preprocessing (Algorithm 1: the
host-to-device copy and its dispatch), per traced fit: the mean length
of the program's ``svm.preprocess`` spans."""

from bench import program_trace

CELL = "solo_nu_1m"


def read(ctx):
    return program_trace.mean_ms(program_trace.of_cell(CELL),
                                 "svm.preprocess")
