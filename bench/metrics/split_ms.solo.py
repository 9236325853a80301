"""Milliseconds of ``SaddleNuSVC.fit``'s numpy class split, per traced
fit: the mean length of the program's ``svm.split`` spans."""

from bench import program_trace

CELL = "solo_nu_1m"


def read(ctx):
    return program_trace.mean_ms(program_trace.of_cell(CELL), "svm.split")
