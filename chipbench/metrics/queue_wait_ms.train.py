"""Host milliseconds per step the consumer waited on the input pipeline's
queue (the program's ``data.queue_wait`` spans, inside ``next_batch``)."""


def read(r):
    n = r.trace.count("next_batch")
    if not n or not r.trace.program_count("data.queue_wait"):
        return None
    return 1e3 * r.trace.program_host_in("data.queue_wait") / n
