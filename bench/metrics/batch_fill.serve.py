"""Decided requests per flush over the micro-batch width, in %, from the
engine's flush-batch histogram over the window."""


def read(layer):
    if not layer.batch_parts:
        return None
    return 100.0 * layer.batch_requests / (layer.batch_parts * layer.micro_batch)
