"""Share of the window's serving steps that were launched while the step
before was still in flight: the program's ``serving.ragged_step`` spans
whose ``in_flight`` attribute is 1, over all of them that carry the
attribute. The engine writes it at the enqueue from its own state (is a
launched step's result still unread), so it says in how many rounds the
host's scheduling, packing and transfers ran beside the device's work
and not before it. A program whose spans lack ``in_flight`` gives
nothing to read."""


def read(record, cell):
    xs = [s["args"]["in_flight"] for s in record.get("spans", ())
          if s["name"] == "serving.ragged_step"
          and "in_flight" in (s.get("args") or {})]
    return 100.0 * sum(1 for x in xs if x) / len(xs) if xs else None
