"""The trace reduction on hand-made intervals."""
import chipcells  # noqa: F401  (puts the benchmark on the path)
import xplane
from xplane import Op, Reduced


def op(name, a, b, scope=""):
    return Op(name, a, b, scope)


def test_short_name_and_scopes():
    assert xplane.short_name(
        "%topk_ef_sparse.17 = (f32[8,32]) custom-call(f32[8,2048] %a)") \
        == "topk_ef_sparse"
    hlo = ('  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, '
           'metadata={op_name="jit(r)/ef_select/add" source_line=3}\n'
           '  ROOT %t = (f32[8]) tuple(%fusion.3)\n')
    assert xplane.op_scopes(hlo) == {"fusion.3": "jit(r)/ef_select/add"}


def test_containers_leave_their_gaps_idle():
    ops = [op("while", 0, 100), op("fusion", 0, 10), op("fusion", 50, 60),
           op("copy", 100, 120)]
    leaves = xplane.leaves(ops)
    assert [(o.start, o.end) for o in leaves] == [(0, 10), (50, 60),
                                                  (100, 120)]
    red = Reduced({0: leaves}, [("dispatch", 0, 200)], (0, 200))
    assert red.busy_s() == 40 / 1e9
    assert red.window_s == 200 / 1e9


def test_sums_match_name_or_scope_and_exposed_excludes_overlap():
    chip = [op("topk_ef_sparse", 0, 30), op("fusion", 30, 40, "a/ef_select/b"),
            op("all-gather-start", 40, 41), op("fusion", 45, 60),
            op("all-gather-done", 70, 71)]
    asyncs = [op("all-gather-start", 40, 71)]
    red = Reduced({0: chip}, [], (0, 100), {0: asyncs})
    assert red.sum_s(0, r"^topk_ef_sparse$", "ef_select") == 40 / 1e9
    assert red.sum_s(0, r"^all-gather", with_async=True) == 31 / 1e9
    # the gather's span 40-71 overlaps the fusion at 45-60
    assert red.exposed_s(0, r"^all-gather", with_async=True) == 16 / 1e9


def test_breakdown_names_gaps_by_host_span():
    chip = [op("fusion", 10, 20), op("topk_ef_sparse", 50, 90)]
    spans = [("stage", 0, 25), ("dispatch", 25, 45), ("sync", 45, 100)]
    bd = Reduced({0: chip}, spans, (0, 100)).breakdown()
    assert bd["device_ops"][0] == ["topk_ef_sparse", 40 / 1e9]
    assert bd["idle_gaps"][0] == ["dispatch", 30 / 1e9]
    assert len(bd["idle_gaps"]) == 3
