"""/worker/stats polled at 1 Hz: the most pages held at once by live
sequences (every tenant of the memory snapshot but the prefix cache's own,
`cache`), as a percentage of the pool."""


def read(ctx, args):
    peaks = []
    for _, stats in ctx.snapshots:
        mem = stats.get("memory") or {}
        total = (mem.get("pool") or {}).get("total_pages")
        by_tenant = mem.get("device_pages_by_tenant")
        if not total or by_tenant is None:
            continue
        live = sum(n for tenant, n in by_tenant.items() if tenant != "cache")
        peaks.append(100.0 * live / total)
    return max(peaks) if peaks else None
