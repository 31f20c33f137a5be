"""Dataset feed: bytes put to the device over the payload's bytes
(``feed_batch``'s ``put`` over its ``payload``, each summed over the window):
what a piece slot a record, zero-padded to ``record_bytes``, costs the host's
copies, the link and the device's memory."""

from layers import feed_events


def read(run):
    put = feed_events.summed(run, "feed_batch", "put")
    payload = feed_events.summed(run, "feed_batch", "payload")
    return None if not put or not payload else put / payload
