"""Mean duration of the system's own `serve.prefill` spans in the window:
host time of the eager prefill together with the persistence of new
prefixes' K rows (the span covers both)."""


def read(run):
    d = [s["dur_us"] for s in run.spans if s["name"] == "serve.prefill"]
    return sum(d) / len(d) / 1e3 if d else None
