"""Mean time per dataset an I/O thread spends sending it to staging: the
program's ``client.send`` spans (write request, blocks, client sync),
every attempt counted, over the datasets they sent."""


def read(run):
    try:
        from repro import obs
    except ImportError:          # a program without spans
        return None
    sends = obs.spans("client.send")
    if not sends:
        return None
    datasets = {s.attrs["ds"] for s in sends}
    return 1e3 * sum(s.seconds for s in sends) / len(datasets)
