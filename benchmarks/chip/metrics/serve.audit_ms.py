"""Mean host time of the governor's windowed audits in the window
(`online.audit_seconds`, published by the system's auditor)."""


def read(run):
    s = run.series.get("online.audit_seconds", [])
    return 1e3 * sum(v for _, v in s) / len(s) if s else None
