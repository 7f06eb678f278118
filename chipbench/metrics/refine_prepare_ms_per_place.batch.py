"""Mean time per placement in the program's ``refine.prepare`` span: the
host's work in each device refine up to the enqueue (padding, sparse
rows, device copies, the launch), in ms."""
from chipbench import span_records


def read(rec):
    return span_records.ms_per_place(rec, "refine.prepare")
