"""Share of prompt tokens that were served from pages another request
had already filled: shared header pages x page size over prompt tokens,
summed over the generations that finished inside the window."""


def read(run):
    page = run.config["batcher"]["page_size"]
    prompt = sum(s["prompt_tokens"] for s in run.summaries)
    if not prompt:
        return None
    shared = sum(s["header_pages_shared"] for s in run.summaries) * page
    return 100.0 * shared / prompt
