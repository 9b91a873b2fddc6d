"""The serving step's share of the roofline that bounds a decode step:
the least time the chip could take to move the bytes a step must move
(the layer weights once a pass, the live KV pages of every cache layer,
the head's weights: ``lib/serve_bytes.py``, from the attributes of the
program's ``serving.ragged_step`` span and the page's size by the
configuration file) over the time the step had the
device: the median of the ``serving.ragged_step`` span (the enqueue,
which the device starts inside) plus the ``serving.device_wait`` span
that follows it. Medians over the window's steps, least and time each.
A step that packs a prefill chunk is bound by compute and reads low
here; the cell this is reported in runs one in about fifty steps. A
program whose span lacks ``passes``, ``cache_layers`` or
``weight_bytes`` gives nothing to read."""
from lib import serve_bytes, stats


def read(record, cell):
    if not cell.peaks:
        return None
    page = serve_bytes.config_page_bytes(cell.config)
    waits = sorted((s["ts"], s["dur"]) for s in record.get("spans", ())
                   if s["name"] == "serving.device_wait")
    least, took, k = [], [], 0
    for s in serve_bytes.step_spans(record):
        end = s["ts"] + s["dur"]
        while k < len(waits) and waits[k][0] < end:
            k += 1
        if k == len(waits):
            break
        least.append(serve_bytes.step_least_bytes(s["args"], page,
                                                  cell.config)
                     / cell.peaks["hbm_bytes_per_s"])
        took.append((s["dur"] + waits[k][1]) / 1e6)
    if not took:
        return None
    cell.log("step_hbm_roofline: %d steps; median least %.3f ms of "
             "median %.3f ms (enqueue + device wait)"
             % (len(took), 1e3 * stats.median(least),
                1e3 * stats.median(took)))
    return 100.0 * stats.median(least) / stats.median(took)
