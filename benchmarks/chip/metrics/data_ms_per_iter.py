"""Host milliseconds per iteration in the data nodes' ``microbatches()``
calls, from the benchmark's own span around them."""


def read(rec):
    return 1000.0 * rec.data_s / rec.iterations
