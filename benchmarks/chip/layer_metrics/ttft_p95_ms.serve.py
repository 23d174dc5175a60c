"""95th percentile (nearest rank), over the requests due in the window, of the
runner's stamp of the first token (taken when the ``engine.step()`` that
produced it returns) minus the time the request was DUE. What a chat user
feels, and not an end-to-end metric only because 51 s hold 76 requests at
today's rate: between runs of identical traffic it spread by 5-15 % (my chip
runs, PR 23), more than any admissible bound covers."""


def read(record):
    return record.end_to_end.get("ttft_p95_ms")
