"""input: seconds in the fit's first train.input.first_batch span: from a
DevicePrefetcher's construction to its first batch in the consumer's hands
(the dataset's start, the first read, the first placement). Moves setup_s."""


def read(run):
    from benchlib import setup_trace
    return setup_trace.fit_span_s("train.input.first_batch", first=True)
