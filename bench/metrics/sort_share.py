"""Share of the ingest programs' device time spent in HLO sort ops (the
structure build), from the profiler trace."""


def read(record: dict):
    role = (record.get("trace") or {}).get("roles", {}).get("ingest")
    if not role or role["device_s"] <= 0:
        return None
    return 100.0 * role["sort_s"] / role["device_s"]
