"""Pages the per-head ragged-attention kernel reads a grid step: sum
``ragged_page_visits`` over sum ``ragged_visits`` of the window's
``serving.ragged_step`` spans. Both are counted on the host from the
step's own arrays by the kernel's rule, for one cache layer of a model
with per-head K and V pools: ``ragged_page_visits`` the live (row, page)
pairs, ``ragged_visits`` the (row, group of pages) visits of a head
block, each a grid step. 1 is a kernel that takes a grid step a page
(what N = 1 does, where a page of every KV head is already large); the
more of a decode row's pages share a step, the higher. A program whose
spans lack the attributes (one that visits a page a step) gives nothing
to read."""


def read(record, cell):
    pages = visits = 0
    for s in record.get("spans", ()):
        a = s.get("args") or {}
        if s["name"] == "serving.ragged_step" and a.get("ragged_visits"):
            pages += a["ragged_page_visits"]
            visits += a["ragged_visits"]
    return pages / visits if visits else None
