"""Mean time per placement in the program's ``weights`` span: Eq. (1)
route weights derived on a weight-cache miss (full, delta or lazy
adapter), on the host, in ms."""
from chipbench import span_records


def read(rec):
    return span_records.ms_per_place(rec, "weights")
