"""Store query: share of the HBM bandwidth roofline that
``query_pairs`` reaches. The least bytes of a call are the real
labels of both ends of every pair plus the pair itself
(`bench.data.roofline.query_bytes`, not the padded label capacity),
averaged over the calls made in the traced window; the time is the
mean device time of a ``query_pairs`` launch there."""

from bench.data import roofline

MODULES = ("jit_query_pairs",)


def read(record, trace, ctx):
    launches = trace.launch_s(MODULES)
    calls = record.get("traced_calls")
    if not launches or not calls or ctx.peaks is None:
        return None
    least = record["traced_bytes"] / calls
    return roofline.share_pct(least, sum(launches) / len(launches),
                              ctx.peaks.hbm_bytes_per_s)
