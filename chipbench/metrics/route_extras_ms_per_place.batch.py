"""Mean time per placement in the program's ``distance.extras`` span:
the lazy torus adapter pricing, in one vectorised route walk, the Eq. (1)
extras of the node pairs whose routes touch a penalised node, on the
host, in ms."""
from chipbench import span_records


def read(rec):
    return span_records.ms_per_place(rec, "distance.extras")
