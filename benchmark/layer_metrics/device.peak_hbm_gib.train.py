"""Peak device memory of the fullest chip: the larger of the allocator's
high-water mark (``memory_stats()["peak_bytes_in_use"]``, which on this
stack misses a running program's temporaries) and the compiled step's own
``memory_analysis()`` total (arguments + outputs + temporaries - aliased).
The reader logs which of the two it was. Without the analysis (the kind
could not reach the compiled step) it reports nothing: the allocator's
mark alone is a different and much smaller quantity."""


def read(record, cell):
    stat = record.get("memory_peak_bytes") or 0
    analysed = record.get("memory_analysis_bytes") or 0
    if not analysed:
        return None
    cell.log("peak_hbm: allocator %d B, memory_analysis %d B; reporting "
             "the %s" % (stat, analysed, "allocator's" if stat >= analysed
                         else "analysis"))
    return max(stat, analysed) / 2.0 ** 30
