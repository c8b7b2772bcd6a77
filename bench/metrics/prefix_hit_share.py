"""KV page pool: share of the prompt tokens of the window's requests that
were served from cached prefix pages (pool prefix hits x page size over
all prompt tokens), in percent."""


def read(w):
    if w.kind != "serve":
        return None
    total = sum(len(r.prompt) for r in w.requests)
    if total <= 0:
        return None
    c = w.counters
    return 100.0 * c["prefix_hit_pages"] * c["page_size"] / total
