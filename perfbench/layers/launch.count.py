"""Route ladder: device launches per request, mean."""


def read(run):
    n = [len(r["records"]) for r in run["requests"] if "records" in r]
    return sum(n) / len(n) if n else None
