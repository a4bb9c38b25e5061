"""Host milliseconds per step the consumer spent putting the batch on the
device (the program's ``data.to_device`` spans, inside ``next_batch``)."""


def read(r):
    n = r.trace.count("next_batch")
    if not n or not r.trace.program_count("data.to_device"):
        return None
    return 1e3 * r.trace.program_host_in("data.to_device") / n
