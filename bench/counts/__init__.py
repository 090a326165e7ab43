"""Operations and bytes the work needs, computed from shapes.

One module per model family (named by a configuration's ``count`` key) and
per optimizer update (named by a traffic mix's ``update_count`` key).
"""
