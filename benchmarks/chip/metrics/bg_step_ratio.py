"""Median step time while a background dump is in flight over the median
step time with none."""
import statistics


def read(rec):
    on = [s["t1"] - s["t0"] for s in rec["spans"]
          if s["name"] == "step" and s.get("overlapped") is True]
    off = [s["t1"] - s["t0"] for s in rec["spans"]
           if s["name"] == "step" and s.get("overlapped") is False]
    if not on or not off:
        return None
    return statistics.median(on) / statistics.median(off)
