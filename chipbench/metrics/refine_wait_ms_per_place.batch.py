"""Mean time per placement in the program's ``refine.wait`` span: from
each device refine's enqueue to its result on the host (the device's
work and the copy back), in ms."""
from chipbench import span_records


def read(rec):
    return span_records.ms_per_place(rec, "refine.wait")
