"""Share of the rows the expert layers' grouped matmuls run over that
carry no live (token, expert) pair: 1 - ``moe_pairs`` / ``moe_rows``,
mean over the window's ``serving.ragged_step`` spans. ``moe_rows`` is
static (every pair the token budget could route, and a 128-row block of
slack an expert), ``moe_pairs`` the live tokens' pairs, both counted by
the program on the host. A program whose span lacks them gives nothing."""


def read(record, cell):
    xs = [1.0 - s["args"]["moe_pairs"] / s["args"]["moe_rows"]
          for s in record.get("spans", ())
          if s["name"] == "serving.ragged_step"
          and s["args"].get("moe_rows")]
    return 100.0 * sum(xs) / len(xs) if xs else None
