"""The window's placements' hop-bytes over those of Slurm's default
block placement of the same jobs, both by the reference in float64."""


def read(rec):
    q = rec.get("quality")
    if not q or not q["block_hop_bytes"]:
        return None
    return q["hop_bytes"] / q["block_hop_bytes"]
