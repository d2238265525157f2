"""GOPs per stripe the coalescer committed in the window (4 is a full
RAID-6 stripe; straggler drains make shorter ones)."""


def read(run):
    stripes = run.stamps.get("committed")
    if not stripes:
        return None
    return sum(len(st.blocks) for st in stripes) / len(stripes)
