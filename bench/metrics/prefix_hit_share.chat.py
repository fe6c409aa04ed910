"""Share of the window's admitted prompt tokens served from the prefix
cache: sum of Request.prefix_pages x page_size over sum of prompt tokens."""

from bench import readers


def read(facts):
    return readers.ratio(facts, "prefix_tokens", "prompt_tokens")
