"""Mean trainer-thread time inside ``store.commit_step`` per step, in ms:
the benchmark's span around the trainer's store method, over the window."""


def read(run):
    spans = run["commit_s"]
    return 1e3 * sum(spans) / len(spans) if spans else None
