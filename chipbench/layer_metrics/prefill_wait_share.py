"""Serving engine: the share of request latency from a request taking an
engine slot to its first token (the engine's ``first_token`` spans: its
prefill, whole or in chunks interleaved with other slots' decode steps),
summed over the window's requests, over the finished requests' summed
latency (due time to result).  With ``queue_wait_share`` it splits RLat
into queue, prefill and decode.  None where the program writes no such
spans."""


def read(record):
    firsts = [s for s in record.get("spans") or []
              if s.get("name") == "first_token" and s.get("t_end") is not None]
    total = sum(r["r_end"] - r["due"] for r in record.get("requests") or []
                if r.get("ok"))
    if not firsts or total <= 0:
        return None
    return 100.0 * sum(s["t_end"] - s["t_start"] for s in firsts) / total
