"""Pages the latent ragged-attention kernel reads a grid step: sum
``latent_page_visits`` over sum ``latent_visits`` of the window's
``serving.ragged_step`` spans. Both are counted on the host from the
step's own arrays by the kernel's rule, for one cache layer:
``latent_page_visits`` the (q block, row, page) triples the step's
tokens see, ``latent_visits`` the kernel's grid steps, each a group of
up to ``_LATENT_PAGES`` of a row's pages (a null visit counts once in
both). 1 is a kernel that takes a grid step a page; the more a decode
row's pages share a step, the higher. A program whose spans lack the
attributes (one that visits a page a step) gives nothing to read."""


def read(record, cell):
    pages = visits = 0
    for s in record.get("spans", ()):
        a = s.get("args") or {}
        if s["name"] == "serving.ragged_step" and a.get("latent_visits"):
            pages += a["latent_page_visits"]
            visits += a["latent_visits"]
    return pages / visits if visits else None
