"""Checkpoint save / resume: host bytes written or copied between HBM and
the store over the object's (x), by ``device_save_bytes_total``: what the
device -> host copies brought, what was copied host to host, what was written
into the store. 2.0 is the floor jax allows (no copy into a caller's
buffer): one pass out of the device, one into the store. Median per
operation."""

from layers import save_events


def read(run):
    return save_events.median(
        (op.counted["save_d2h"] + op.counted["save_copied"]
         + op.counted["save_stored"]) / op.nbytes
        for op in run.ops
        if getattr(op, "counted", None) and op.nbytes
        and "save_d2h" in op.counted)
