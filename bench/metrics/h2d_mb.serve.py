"""Megabytes of operands one full batch ships to the device: the pooled
host buffers (`pooled_bytes`) of every array the device stage transfers,
at the largest bucket's high-water mark."""


def read(rec):
    if not rec.get("h2d_bytes"):
        return None
    return rec["h2d_bytes"] / 1e6
