"""Mean time per placement in the program's ``candidates`` span: tofa's
candidate node sets (windows, balls, subset growth) on a memo miss, in
ms."""
from chipbench import span_records


def read(rec):
    return span_records.ms_per_place(rec, "candidates")
