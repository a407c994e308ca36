"""Mean queue wait of a request, in ms: from its arrival to the dispatch
of its bucket, over the ``fft.bucket.form`` spans that start in the
window (sum of ``wait_mean_ms`` times ``n``, over the sum of ``n``)."""

from bench import spans


def read(run):
    sp = spans.of(run)
    if sp is None:
        return None
    forms = [args for _, _, args, _ in spans.started(sp, "fft.bucket.form")]
    n = sum(a["n"] for a in forms)
    return sum(a["wait_mean_ms"] * a["n"] for a in forms) / n if n else None
