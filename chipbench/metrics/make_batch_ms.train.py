"""Host milliseconds the input pipeline's producer thread takes to make one
batch (the program's ``data.make_batch`` spans in the traced window)."""


def read(r):
    n = r.trace.program_count("data.make_batch")
    return 1e3 * r.trace.program_host_in("data.make_batch") / n if n else None
