"""paging.peak_pages_in_use (%): the page arena's peak pages in use over its
usable pages, at the window's end. Layer: paging. Moves serve_tokens_per_s."""


def read(ctx):
    end = ctx["counters"].get("end", {})
    if not end.get("usable_pages"):
        return None
    return 100.0 * end["peak_pages_in_use"] / end["usable_pages"]
