"""Cache manager: the most pages held by live requests at any step of the
window, as a share of the pool's allocatable pages (idle prefix-cache pages
are not counted: the pool gives them up under pressure)."""


def read(run):
    if not run.pool_pages:
        return None
    return 100.0 * run.pages_peak / run.pool_pages
