"""Median time to embed one wave, in milliseconds: hash tokenization on
the host, the query encoder's dispatch and the copy of the embeddings to
the host (`repro.serving.encoder.embed_texts`)."""
from harness import stats


def read(run):
    if not run.waves:
        return None
    return 1000.0 * stats.percentile([w["encode_s"] for w in run.waves], 50)
