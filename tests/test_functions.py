"""Transform/scalar function library correctness.

Datetime functions are differential-tested against python's datetime module (UTC) over
random epochs including pre-1970; string functions against straight python. End-to-end
queries exercise the device kernel path for calendar math (reference analog:
DateTimeFunctionsTest / StringFunctionsTest in pinot-common, and the transform-function
suites in pinot-core).
"""

import datetime as dt

import numpy as np
import pytest

from pinot_tpu.engine.expr import eval_expr
from pinot_tpu.sql.parser import Parser


def expr(sql_expr):
    return Parser(f"SELECT {sql_expr} FROM t").parse().select[0][0]


def ev(sql_expr, env=None, xp=np):
    return eval_expr(expr(sql_expr), env or {}, xp)


@pytest.fixture(scope="module")
def epochs():
    rng = np.random.default_rng(3)
    ms = rng.integers(-5_000_000_000_000, 5_000_000_000_000, 500).astype(np.int64)
    fixed = np.array([0, 1, -1, 86_399_999, 86_400_000, -86_400_000,
                      951_782_400_000,   # 2000-02-29
                      4_107_542_400_000  # 2100-02-28 (non-leap century)
                      ], dtype=np.int64)
    return np.concatenate([fixed, ms])


def utc(ms):
    return dt.datetime(1970, 1, 1, tzinfo=dt.timezone.utc) + dt.timedelta(milliseconds=int(ms))


def test_calendar_fields(epochs):
    env = {"ts": epochs}
    got = {name: np.asarray(ev(f"{name}(ts)", env))
           for name in ("year", "month", "dayofmonth", "dayofyear", "dayofweek",
                        "hour", "minute", "second", "millisecond", "quarter", "week")}
    for i, ms in enumerate(epochs):
        d = utc(ms)
        iso = d.isocalendar()
        assert got["year"][i] == d.year, ms
        assert got["month"][i] == d.month, ms
        assert got["dayofmonth"][i] == d.day, ms
        assert got["dayofyear"][i] == d.timetuple().tm_yday, ms
        assert got["dayofweek"][i] == d.isoweekday(), ms
        assert got["hour"][i] == d.hour, ms
        assert got["minute"][i] == d.minute, ms
        assert got["second"][i] == d.second, ms
        assert got["millisecond"][i] == int(ms) % 1000, ms
        assert got["quarter"][i] == (d.month - 1) // 3 + 1, ms
        assert got["week"][i] == iso[1], ms


def test_calendar_fields_on_jax(epochs):
    # The scan path ships 64-bit epochs to the device decomposed (or falls back to host —
    # planner rejects >int32 columns); under x64 the traced math must match numpy exactly.
    import jax
    import jax.numpy as jnp
    with jax.enable_x64(True):
        host = np.asarray(ev("year(ts)", {"ts": epochs}))
        dev = np.asarray(ev("year(ts)", {"ts": jnp.asarray(epochs)}, xp=jnp))
        np.testing.assert_array_equal(host, dev)
        np.testing.assert_array_equal(
            np.asarray(ev("week(ts)", {"ts": epochs})),
            np.asarray(ev("week(ts)", {"ts": jnp.asarray(epochs)}, xp=jnp)))


def test_datetrunc(epochs):
    env = {"ts": epochs}
    for unit, fn in [
        ("day", lambda d: d.replace(hour=0, minute=0, second=0, microsecond=0)),
        ("month", lambda d: d.replace(day=1, hour=0, minute=0, second=0, microsecond=0)),
        ("year", lambda d: d.replace(month=1, day=1, hour=0, minute=0, second=0,
                                     microsecond=0)),
    ]:
        got = np.asarray(ev(f"datetrunc('{unit}', ts)", env))
        for i, ms in enumerate(epochs):
            want = int(fn(utc(ms)).timestamp() * 1000)
            assert got[i] == want, (unit, ms)


def test_datetrunc_week_is_monday(epochs):
    got = np.asarray(ev("datetrunc('week', ts)", {"ts": epochs}))
    for i, ms in enumerate(epochs):
        d = utc(got[i])
        assert d.isoweekday() == 1 and d.hour == 0 and d.minute == 0
        assert got[i] <= ms < got[i] + 7 * 86_400_000


def test_epoch_conversions():
    assert ev("toepochdays(ts)", {"ts": np.int64(86_400_000 * 3 + 5)}) == 3
    assert ev("fromepochhours(ts)", {"ts": np.int64(2)}) == 7_200_000
    assert ev("toepochminutesbucket(ts, 10)", {"ts": np.int64(60_000 * 25)}) == 2
    assert ev("timeconvert(ts, 'MILLISECONDS', 'SECONDS')", {"ts": np.int64(5999)}) == 5


def test_datetimeconvert_epoch_roundtrip():
    ts = np.array([1_577_836_800_000, 1_577_923_200_123], dtype=np.int64)  # 2020-01-01/02
    days = np.asarray(ev("datetimeconvert(ts, '1:MILLISECONDS:EPOCH', '1:DAYS:EPOCH', '1:DAYS')",
                         {"ts": ts}))
    np.testing.assert_array_equal(days, [18262, 18263])
    sdf = ev("datetimeconvert(ts, '1:MILLISECONDS:EPOCH', "
             "'1:DAYS:SIMPLE_DATE_FORMAT:yyyy-MM-dd', '1:DAYS')", {"ts": ts})
    assert list(sdf) == ["2020-01-01", "2020-01-02"]


def test_todatetime_fromdatetime_roundtrip():
    ts = np.array([1_577_836_800_000, 1_609_459_199_000], dtype=np.int64)
    s = ev("todatetime(ts, 'yyyy-MM-dd HH:mm:ss')", {"ts": ts})
    back = np.asarray(ev("fromdatetime(s, 'yyyy-MM-dd HH:mm:ss')", {"s": s}))
    np.testing.assert_array_equal(back, ts)


def test_string_functions():
    v = np.asarray(["Hello World", "  pad  ", "abc", ""], dtype=object)
    env = {"s": v}
    assert list(ev("upper(s)", env)) == ["HELLO WORLD", "  PAD  ", "ABC", ""]
    assert list(ev("lower(s)", env)) == ["hello world", "  pad  ", "abc", ""]
    assert list(ev("reverse(s)", env)) == ["dlroW olleH", "  dap  ", "cba", ""]
    assert list(ev("length(s)", env)) == [11, 7, 3, 0]
    assert list(ev("trim(s)", env)) == ["Hello World", "pad", "abc", ""]
    assert list(ev("substr(s, 0, 5)", env)) == ["Hello", "  pad", "abc", ""]
    assert list(ev("substr(s, 6)", env)) == ["World", " ", "", ""]
    assert list(ev("replace(s, 'l', 'L')", env)) == ["HeLLo WorLd", "  pad  ", "abc", ""]
    assert list(ev("startswith(s, 'He')", env)) == [True, False, False, False]
    assert list(ev("contains(s, 'o')", env)) == [True, False, False, False]
    assert list(ev("strpos(s, 'o')", env)) == [4, -1, -1, -1]
    assert list(ev("strpos(s, 'o', 2)", env)) == [7, -1, -1, -1]
    assert list(ev("lpad(s, 5, '*')", env)) == ["Hello", "  pad", "**abc", "*****"]
    assert list(ev("rpad(s, 4, '-')", env)) == ["Hell", "  pa", "abc-", "----"]
    assert list(ev("splitpart(s, ' ', 1)", env)) == ["World", "", "null", "null"]


def test_concat_and_codepoints():
    a = np.asarray(["x", "y"], dtype=object)
    b = np.asarray(["1", "2"], dtype=object)
    assert list(ev("concat(a, b)", {"a": a, "b": b})) == ["x1", "y2"]
    assert list(ev("concat(a, b, '-')", {"a": a, "b": b})) == ["x-1", "y-2"]
    assert list(ev("concat_ws('-', a, b)", {"a": a, "b": b})) == ["x-1", "y-2"]
    assert ev("codepoint(s)", {"s": "A"}) == 65
    assert ev("chr(n)", {"n": 66}) == "B"


def test_regexp_functions():
    v = np.asarray(["foo123bar", "nope"], dtype=object)
    assert list(ev("regexp_extract(s, '[0-9]+')", {"s": v})) == ["123", ""]
    assert list(ev("regexp_replace(s, '[0-9]+', '#')", {"s": v})) == ["foo#bar", "nope"]


def test_hash_functions():
    import hashlib
    v = np.asarray(["abc"], dtype=object)
    assert ev("md5(s)", {"s": v})[0] == hashlib.md5(b"abc").hexdigest()
    assert ev("sha256(s)", {"s": v})[0] == hashlib.sha256(b"abc").hexdigest()


def test_null_functions():
    a = np.array([1.0, np.nan, 3.0])
    b = np.array([9.0, 8.0, 7.0])
    np.testing.assert_array_equal(ev("coalesce(a, b)", {"a": a, "b": b}), [1.0, 8.0, 3.0])
    got = ev("nullif(a, 1.0)", {"a": a})
    assert np.isnan(got[0]) and np.isnan(got[1]) and got[2] == 3.0


def test_arith_extras():
    v = np.array([-2.5, 0.0, 3.7])
    np.testing.assert_array_equal(ev("sign(v)", {"v": v}), [-1.0, 0.0, 1.0])
    np.testing.assert_allclose(ev("truncate(v, 0)", {"v": v}), [-2.0, 0.0, 3.0])
    np.testing.assert_allclose(ev("atan2(v, v)", {"v": np.array([1.0])}), [np.pi / 4])
    np.testing.assert_allclose(ev("degrees(v)", {"v": np.array([np.pi])}), [180.0])


# -- end-to-end through the query engine -------------------------------------

@pytest.fixture(scope="module")
def time_env(tmp_path_factory):
    from pinot_tpu.query.executor import execute_query
    from pinot_tpu.schema import DataType, Schema, date_time, dimension, metric
    from pinot_tpu.segment import SegmentBuilder, SegmentGeneratorConfig, load_segment

    rng = np.random.default_rng(11)
    n = 2000
    start = 1_560_000_000_000
    cols = {
        "ts": (start + rng.integers(0, 400 * 86_400_000, n)).astype(np.int64),
        "site": [f"site{i}" for i in rng.integers(0, 4, n)],
        "clicks": rng.integers(0, 100, n).astype(np.int32),
    }
    schema = Schema("events", [
        date_time("ts", DataType.TIMESTAMP),
        dimension("site", DataType.STRING),
        metric("clicks", DataType.INT),
    ])
    out = tmp_path_factory.mktemp("timeseg")
    seg = load_segment(SegmentBuilder(schema, SegmentGeneratorConfig()).build(
        cols, str(out), "events_0"))
    return [seg], cols, execute_query


def test_group_by_year(time_env):
    segments, cols, execute_query = time_env
    res = execute_query(segments, "SELECT YEAR(ts), COUNT(*) FROM events GROUP BY YEAR(ts)")
    want = {}
    for ms in cols["ts"]:
        y = utc(ms).year
        want[y] = want.get(y, 0) + 1
    got = {int(r[0]): int(r[1]) for r in res.rows}
    assert got == want


def test_filter_on_datetrunc(time_env):
    segments, cols, execute_query = time_env
    res = execute_query(
        segments,
        "SELECT COUNT(*) FROM events WHERE DATETRUNC('year', ts) = 1577836800000")
    want = sum(1 for ms in cols["ts"] if utc(ms).year == 2020)
    assert int(res.rows[0][0]) == want


def test_select_todatetime(time_env):
    segments, cols, execute_query = time_env
    res = execute_query(segments,
                        "SELECT TODATETIME(ts, 'yyyy-MM-dd') FROM events LIMIT 5")
    for row in res.rows:
        assert len(row[0]) == 10 and row[0][4] == "-"


# -- new breadth: MV reductions, codecs, cot ----------------------------------

def _mv_env():
    return {"a": np.array([np.array([1.0, 2.0, 3.0]), np.array([5.0]),
                           np.array([])], dtype=object),
            "s": np.array(["café com leite", "a&b=c", None], dtype=object)}


def test_array_reductions():
    env = _mv_env()
    assert ev("arraysum(a)", env).tolist() == [6.0, 5.0, 0.0]
    assert ev("arraymax(a)", env)[:2].tolist() == [3.0, 5.0]
    assert ev("arraymin(a)", env)[:2].tolist() == [1.0, 5.0]
    assert ev("arrayaverage(a)", env)[0] == pytest.approx(2.0)
    assert np.isnan(ev("arrayaverage(a)", env)[2])


def test_array_distinct_sort_index():
    env = {"a": np.array([np.array([3, 1, 3, 2]), np.array([7])], dtype=object)}
    d = ev("arraydistinct(a)", env)
    assert d[0].tolist() == [3, 1, 2]
    assert ev("arraysortasc(a)", env)[0].tolist() == [1, 2, 3, 3]
    assert ev("arraysortdesc(a)", env)[0].tolist() == [3, 3, 2, 1]
    assert ev("arrayindexof(a, 2)", env).tolist() == [3, -1]
    assert ev("arraycontains(a, 7)", env).tolist() == [False, True]


def test_base64_and_url_codecs():
    import base64
    import urllib.parse
    env = _mv_env()
    enc = ev("tobase64(s)", env)
    assert enc[0] == base64.b64encode("café com leite".encode()).decode()
    assert enc[2] is None
    back = ev("frombase64(tobase64(s))", env)
    assert back[0] == "café com leite"
    u = ev("encodeurl(s)", env)
    assert u[1] == urllib.parse.quote("a&b=c", safe="")
    assert ev("decodeurl(encodeurl(s))", env)[1] == "a&b=c"


def test_cot():
    assert ev("cot(x)", {"x": np.array([1.0])})[0] == pytest.approx(1 / np.tan(1.0))


def test_codecs_on_scalar_literals():
    assert ev("tobase64('hello')", {}) == "aGVsbG8="
    assert ev("frombase64('aGVsbG8=')", {}) == "hello"
    assert ev("encodeurl('a b')", {}) == "a%20b"


def test_string_breadth_batch():
    env = {"s": np.array(["hello", "world"], dtype=object)}
    assert ev("repeat(s, 2)", env).tolist() == ["hellohello", "worldworld"]
    assert ev("remove(s, 'l')", env).tolist() == ["heo", "word"]
    assert ev("leftsubstr(s, 3)", env).tolist() == ["hel", "wor"]
    assert ev("rightsubstr(s, 3)", env).tolist() == ["llo", "rld"]
    assert ev("strcmp(s, 'hello')", env).tolist() == [0, 1]
    assert ev("strrpos(s, 'l')", env).tolist() == [3, 3]
    assert ev("hammingdistance(s, 'hella')", env).tolist() == [1, 4]
    assert ev("toascii(s)", env).tolist() == ["hello", "world"]
    assert ev("base64encode(s)", env)[0] == "aGVsbG8="
    assert ev("bytestohex(toutf8(s))", env)[0] == "68656c6c6f"
    assert ev("fromutf8(hextobytes('68656c6c6f'))", {}) == "hello"


def test_timestamp_add_diff():
    ts = 1_700_000_000_000  # 2023-11-14
    env = {"t": np.array([ts], dtype=np.int64)}
    plus_day = ev("timestampadd('DAY', 3, t)", env)
    assert int(plus_day[0]) == ts + 3 * 86_400_000
    plus_month = ev("timestampadd('MONTH', 2, t)", env)
    import datetime as dt
    d0 = dt.datetime.fromtimestamp(ts / 1000, dt.timezone.utc)
    d1 = dt.datetime.fromtimestamp(int(plus_month[0]) / 1000, dt.timezone.utc)
    assert (d1.year, d1.month, d1.day) == (2024, 1, d0.day)
    assert ev("timestampdiff('HOUR', t, timestampadd('HOUR', 7, t))", env)[0] == 7
    assert ev("datediff('MONTH', t, dateadd('MONTH', 5, t))", env)[0] == 5
    # month-end clamping: Jan 31 + 1 month -> Feb 29 (2024 leap)
    jan31 = int(dt.datetime(2024, 1, 31, tzinfo=dt.timezone.utc).timestamp() * 1000)
    feb = ev("timestampadd('MONTH', 1, t2)", {"t2": np.array([jan31], dtype=np.int64)})
    d2 = dt.datetime.fromtimestamp(int(feb[0]) / 1000, dt.timezone.utc)
    assert (d2.month, d2.day) == (2, 29)


def test_array_breadth_batch():
    env = {"a": np.array([np.array([3, 1, 3]), np.array([7, 8])], dtype=object),
           "b": np.array([np.array([1, 9]), np.array([8])], dtype=object)}
    assert ev("arrayreverse(a)", env)[0].tolist() == [3, 1, 3][::-1]
    assert ev("arrayslice(a, 0, 2)", env)[0].tolist() == [3, 1]
    assert ev("arrayremove(a, 3)", env)[0].tolist() == [1, 3]  # first occurrence only
    assert ev("arrayunion(a, b)", env)[0].tolist() == [3, 1, 9]
    assert ev("arrayconcat(a, b)", env)[1].tolist() == [7, 8, 8]
    assert ev("arraysortint(a)", env)[0].tolist() == [1, 3, 3]


def test_jsonpath_aliases():
    env = {"j": np.array(['{"a": {"b": 7, "s": "x"}}'], dtype=object)}
    assert ev("jsonpathlong(j, '$.a.b')", env).tolist() == [7]
    assert ev("jsonpathstring(j, '$.a.s')", env).tolist() == ["x"]
    assert ev("jsonpathdouble(j, '$.a.b')", env).tolist() == [7.0]


def test_function_review_fixes():
    import math
    env = {"s": np.array(["abcabc"], dtype=object)}
    assert ev("repeat(s, '-', 3)", env)[0] == "abcabc-abcabc-abcabc"
    assert ev("strrpos(s, 'bc', 4)", env)[0] == 4  # match may START at fromIndex
    assert ev("timezonehour('America/New_York')", {}) == -5    # at epoch 0, no DST
    assert ev("timezonehour('America/St_Johns')", {}) == -3    # truncate toward zero
    assert ev("timezoneminute('America/St_Johns')", {}) == -30
    j = {"j": np.array(['{"a": 1}'], dtype=object)}
    assert ev("jsonpathlong(j, '$.missing')", j).tolist() == [-(1 << 63)]
    assert math.isnan(ev("jsonpathdouble(j, '$.missing')", j)[0])
