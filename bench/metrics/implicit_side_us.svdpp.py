"""Device time per training step of SVD++'s implicit side, in us: every
traced operation whose text names an array only that side has -- the
implicit table's shape (``f32[num_items + 1,k]``, which no other table of
the cell has), the step's history slots (batch x max_hist rows, or a
(batch, max_hist, ...) array), or a level of the slots' segmented run sums
(half, a quarter, ... of those rows, down to more than twice the batch):
the gathers from Y and its Adagrad table, the slots' updates, sort,
permutation and run sums, and the row write.  The epoch scan's loops,
which carry these arrays and hold the whole step, are left out; so are
the run sums' last levels, whose arrays have the batch's own sizes."""


def read(run):
    if run.trace is None or "svdpp" not in run.ctx:
        return None
    ctx = run.ctx["svdpp"]
    batch, slots = ctx["batch"], ctx["max_hist"]
    rows = [batch * slots >> j for j in range((batch * slots).bit_length())
            if batch * slots >> j > 2 * batch]
    marks = [ctx["implicit_table"], f"[{batch},{slots},", f"[{batch},{slots}]"]
    marks += [f"[{r}{end}" for r in rows for end in (",", "]")]
    seconds = sum(v[1] for name, v in run.trace["ops"].items()
                  if any(m in name for m in marks)
                  and not name.lstrip("%").startswith("while"))
    if not seconds:
        return None
    return 1e6 * seconds / run.ctx["train"]["steps"]
