"""The chunk count the streamed path used on its last call
(``last_stream_chunks``), the largest over the lanes."""


def read(ctx):
    chunks = ctx.cr.cores.last_stream_chunks
    return float(max(chunks.values())) if chunks else None
