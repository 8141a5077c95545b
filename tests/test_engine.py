"""End-to-end engine tests: GraphQL± in, JSON out, over a small social
graph. Mirrors the reference's black-box query suite style
(query/query0_test.go + testutil.CompareJSON)."""

import numpy as np
import pytest

from dgraph_tpu.cluster.coordinator import TxnAborted
from dgraph_tpu.engine import GraphDB

SCHEMA = """
name: string @index(term, exact) @lang .
age: int @index(int) .
friend: [uid] @reverse @count .
owns: uid .
score: float @index(float) .
alive: bool @index(bool) .
dob: datetime @index(year) .
nick: [string] .
"""

RDF = """
<0x1> <name> "Michonne" .
<0x1> <name> "Michona"@pl .
<0x1> <age> "38" .
<0x1> <alive> "true" .
<0x1> <dob> "1910-01-01" .
<0x1> <friend> <0x17> .
<0x1> <friend> <0x18> .
<0x1> <friend> <0x19> .
<0x1> <friend> <0x1f> .
<0x1> <nick> "mich" .
<0x1> <nick> "onne" .
<0x17> <name> "Rick Grimes" .
<0x17> <age> "15" .
<0x17> <friend> <0x1> .
<0x18> <name> "Glenn Rhee" .
<0x18> <age> "15" .
<0x19> <name> "Daryl Dixon" .
<0x19> <age> "17" .
<0x19> <alive> "false" .
<0x1f> <name> "Andrea" .
<0x1f> <age> "19" .
<0x1f> <friend> <0x18> .
<0x1f> <score> "2.5" .
<0x2> <name> "King Lear" .
<0x2> <owns> <0x3> .
<0x3> <name> "Castle" .
"""


@pytest.fixture(scope="module")
def db():
    d = GraphDB(prefer_device=False)
    d.alter(SCHEMA)
    d.mutate(set_nquads=RDF)
    return d


def data(resp):
    return resp["data"]


def test_eq_root_and_children(db):
    r = data(db.query('{ me(func: eq(name, "Michonne")) { name age } }'))
    assert r["me"] == [{"name": "Michonne", "age": 38}]


def test_uid_func(db):
    r = data(db.query("{ me(func: uid(0x17, 0x18)) { name } }"))
    assert r["me"] == [{"name": "Rick Grimes"}, {"name": "Glenn Rhee"}]


def test_one_hop(db):
    r = data(db.query('''{
      me(func: eq(name, "Michonne")) { name friend { name age } }
    }'''))
    friends = r["me"][0]["friend"]
    assert [f["name"] for f in friends] == \
        ["Rick Grimes", "Glenn Rhee", "Daryl Dixon", "Andrea"]


def test_filter_and_or_not(db):
    r = data(db.query('''{
      me(func: eq(name, "Michonne")) {
        friend @filter(eq(age, 15) OR eq(age, 19)) { name }
      }
    }'''))
    assert [f["name"] for f in r["me"][0]["friend"]] == \
        ["Rick Grimes", "Glenn Rhee", "Andrea"]
    r = data(db.query('''{
      me(func: eq(name, "Michonne")) {
        friend @filter(NOT eq(age, 15)) { name }
      }
    }'''))
    assert [f["name"] for f in r["me"][0]["friend"]] == \
        ["Daryl Dixon", "Andrea"]


def test_ineq_root(db):
    r = data(db.query("{ q(func: ge(age, 17)) { name age } }"))
    names = {x["name"] for x in r["q"]}
    assert names == {"Michonne", "Daryl Dixon", "Andrea"}
    r = data(db.query("{ q(func: between(age, 15, 17)) { name } }"))
    assert {x["name"] for x in r["q"]} == \
        {"Rick Grimes", "Glenn Rhee", "Daryl Dixon"}


def test_terms(db):
    r = data(db.query('{ q(func: anyofterms(name, "rick andrea")) { name } }'))
    assert {x["name"] for x in r["q"]} == {"Rick Grimes", "Andrea"}
    r = data(db.query('{ q(func: allofterms(name, "rick grimes")) { name } }'))
    assert [x["name"] for x in r["q"]] == ["Rick Grimes"]


def test_has_and_count(db):
    r = data(db.query("{ q(func: has(friend)) { count(uid) } }"))
    # count(uid) blocks: reference emits [{"count": N}]
    r2 = data(db.query('''{
      me(func: eq(name, "Michonne")) { count(friend) }
    }'''))
    assert r2["me"] == [{"count(friend)": 4}]


def test_count_filter(db):
    r = data(db.query("{ q(func: gt(count(friend), 1)) { name } }"))
    assert {x["name"] for x in r["q"]} == {"Michonne"}


def test_pagination_and_order(db):
    r = data(db.query('''{
      me(func: eq(name, "Michonne")) {
        friend (orderasc: age, first: 2) { name age }
      }
    }'''))
    assert [f["name"] for f in r["me"][0]["friend"]] == \
        ["Rick Grimes", "Glenn Rhee"]
    r = data(db.query('''{
      me(func: eq(name, "Michonne")) {
        friend (orderdesc: age, first: 2) { name age }
      }
    }'''))
    assert [f["age"] for f in r["me"][0]["friend"]] == [19, 17]


def test_root_order(db):
    r = data(db.query("{ q(func: has(age), orderdesc: age, first: 3) { age } }"))
    assert [x["age"] for x in r["q"]] == [38, 19, 17]


def test_reverse_edge(db):
    r = data(db.query('{ q(func: uid(0x18)) { name ~friend { name } } }'))
    assert {x["name"] for x in r["q"][0]["~friend"]} == {"Michonne", "Andrea"}


def test_uid_var_block(db):
    r = data(db.query('''{
      A as var(func: eq(name, "Michonne")) { friend { f as uid } }
      q(func: uid(f)) @filter(NOT uid(A)) { name }
    }'''))
    assert {x["name"] for x in r["q"]} == \
        {"Rick Grimes", "Glenn Rhee", "Daryl Dixon", "Andrea"}


def test_value_var_and_agg(db):
    r = data(db.query('''{
      var(func: has(age)) { a as age }
      q(func: uid(0x1)) {
        mx: max(val(a)) mn: min(val(a)) sm: sum(val(a)) av: avg(val(a))
      }
    }'''))
    # block-level aggregates over the src set {0x1}
    vals = {k: v for d in r["q"] for k, v in d.items()}
    assert vals["mx"] == 38 and vals["mn"] == 38


def test_agg_over_var_block(db):
    r = data(db.query('''{
      var(func: has(age)) { a as age }
      q() { mx: max(val(a)) sm: sum(val(a)) }
    }'''))
    vals = {k: v for d in r["q"] for k, v in d.items()}
    assert vals["mx"] == 38
    assert vals["sm"] == 38 + 15 + 15 + 17 + 19


def test_val_output_and_order_by_val(db):
    r = data(db.query('''{
      var(func: has(age)) { a as age }
      q(func: uid(0x17, 0x18, 0x19), orderdesc: val(a)) { name val(a) }
    }'''))
    assert [x["name"] for x in r["q"]] == \
        ["Daryl Dixon", "Rick Grimes", "Glenn Rhee"]
    assert r["q"][0]["val(a)"] == 17


def test_math(db):
    r = data(db.query('''{
      var(func: has(age)) { a as age double as math(a * 2) }
      q(func: uid(0x19)) { d: val(double) }
    }'''))
    assert r["q"] == [{"d": 34}]


def test_math_since(db):
    """since(): seconds elapsed since a datetime (ref
    query/aggregator.go:353 applySince); datetimes flow into math
    trees as epoch-seconds so comparisons work too."""
    db2 = GraphDB(prefer_device=False)
    db2.alter("joined: datetime .")
    db2.mutate(set_nquads='<0x1> <joined> "2020-01-01T00:00:00Z" .')
    r = data(db2.query('''{
      q(func: uid(0x1)) {
        j as joined
        secs: math(since(j))
        old: math(since(j) > 86400)
      }
    }'''))
    row = r["q"][0]
    # 2020-01-01 is > 6 years before the build's clock, < 100 years
    assert 6 * 365 * 86400 < row["secs"] < 100 * 365 * 86400
    assert row["old"] is True


def test_lang(db):
    r = data(db.query('{ q(func: uid(0x1)) { name@pl name@en:. } }'))
    assert r["q"][0]["name@pl"] == "Michona"


def test_list_values(db):
    r = data(db.query("{ q(func: uid(0x1)) { nick } }"))
    assert sorted(r["q"][0]["nick"]) == ["mich", "onne"]


def test_alias(db):
    r = data(db.query('{ q(func: uid(0x17)) { moniker: name } }'))
    assert r["q"] == [{"moniker": "Rick Grimes"}]


def test_expand_all(db):
    db2 = GraphDB(prefer_device=False)
    db2.alter("name: string .\nage: int .\ntype Person {name age}")
    db2.mutate(set_nquads='''
      <0x1> <name> "A" .
      <0x1> <age> "3" .
      <0x1> <dgraph.type> "Person" .
    ''')
    r = data(db2.query("{ q(func: uid(0x1)) { expand(_all_) } }"))
    assert r["q"][0]["name"] == "A" and r["q"][0]["age"] == 3


def test_recurse(db):
    r = data(db.query('''{
      q(func: uid(0x1)) @recurse(depth: 3) { name friend }
    }'''))
    root = r["q"][0]
    assert root["name"] == "Michonne"
    names = {f["name"] for f in root["friend"]}
    assert names == {"Rick Grimes", "Glenn Rhee", "Daryl Dixon", "Andrea"}
    rick = [f for f in root["friend"] if f["name"] == "Rick Grimes"][0]
    # Michonne appears as Rick's friend but, already visited, is not
    # re-expanded (ref query/recurse.go reachMap behavior)
    mich = rick["friend"][0]
    assert mich["name"] == "Michonne" and "friend" not in mich


def test_shortest(db):
    r = data(db.query('''{
      path as shortest(from: 0x17, to: 0x1f) { friend }
      q(func: uid(path)) { name }
    }'''))
    # 0x17 -> 0x1 -> 0x1f
    chain, cur = [], r["_path_"][0]
    while cur is not None:
        chain.append(cur["uid"])
        cur = next((v for v in cur.values() if isinstance(v, dict)),
                   None)
    assert chain == ["0x17", "0x1", "0x1f"]
    assert {x["name"] for x in r["q"]} == \
        {"Rick Grimes", "Michonne", "Andrea"}


def test_regexp(db):
    r = data(db.query('{ q(func: has(name)) @filter(regexp(name, /Gri/)) { name } }'))
    assert {x["name"] for x in r["q"]} == {"Rick Grimes"}


def test_cascade(db):
    r = data(db.query('''{
      q(func: has(name)) @cascade { name alive }
    }'''))
    assert {x["name"] for x in r["q"]} == {"Michonne", "Daryl Dixon"}


def test_groupby(db):
    r = data(db.query('''{
      q(func: uid(0x1)) { friend @groupby(age) { count(uid) } }
    }'''))
    groups = r["q"][0]["friend"][0]["@groupby"]
    bycount = {g["age"]: g["count"] for g in groups}
    assert bycount == {15: 2, 17: 1, 19: 1}


def test_normalize(db):
    r = data(db.query('''{
      q(func: uid(0x1)) @normalize { n: name friend { fn: name } }
    }'''))
    assert all("n" in x for x in r["q"])


def test_mutation_delete_and_txn():
    d = GraphDB(prefer_device=False)
    d.alter("name: string @index(exact) .\nfriend: [uid] .")
    d.mutate(set_nquads='<0x1> <name> "A" .\n<0x1> <friend> <0x2> .')
    r = data(d.query('{ q(func: uid(0x1)) { name friend {uid} } }'))
    assert r["q"][0]["name"] == "A"
    d.mutate(del_nquads='<0x1> <friend> <0x2> .')
    r = data(d.query('{ q(func: uid(0x1)) { name friend {uid} } }'))
    assert "friend" not in r["q"][0]
    d.mutate(del_nquads='<0x1> <name> * .')
    r = data(d.query('{ q(func: uid(0x1)) { name } }'))
    assert r["q"] == []  # no postings left


def test_value_overwrite_updates_index():
    d = GraphDB(prefer_device=False)
    d.alter("name: string @index(exact) .")
    d.mutate(set_nquads='<0x1> <name> "Old" .')
    d.mutate(set_nquads='<0x1> <name> "New" .')
    assert data(d.query('{ q(func: eq(name, "Old")) { uid } }'))["q"] == []
    assert data(d.query('{ q(func: eq(name, "New")) { uid } }'))["q"] == \
        [{"uid": "0x1"}]


def test_star_delete_clears_overlay_index():
    """Regression: S P * must drop index entries for values that were
    set in the un-rolled-up overlay, not just the base state."""
    d = GraphDB(prefer_device=False)
    d.alter("name: string @index(term) .")
    d.mutate(set_nquads='<0x1> <name> "Ada Lovelace" .')
    d.mutate(del_nquads='<0x1> <name> * .')
    assert data(d.query('{ q(func: anyofterms(name, "ada")) { uid } }'))["q"] == []


def test_txn_conflict():
    d = GraphDB(prefer_device=False)
    d.alter("name: string .")
    t1 = d.new_txn()
    t2 = d.new_txn()
    d.mutate(t1, set_nquads='<0x1> <name> "from-t1" .')
    d.mutate(t2, set_nquads='<0x1> <name> "from-t2" .')
    d.commit(t1)
    with pytest.raises(TxnAborted):
        d.commit(t2)
    r = data(d.query('{ q(func: uid(0x1)) { name } }'))
    assert r["q"] == [{"name": "from-t1"}]


def test_txn_snapshot_isolation():
    d = GraphDB(prefer_device=False)
    d.alter("name: string .")
    d.mutate(set_nquads='<0x1> <name> "v1" .')
    t = d.new_txn()  # snapshot here
    d.mutate(set_nquads='<0x1> <name> "v2" .')
    r = data(d.query('{ q(func: uid(0x1)) { name } }', txn=t))
    assert r["q"] == [{"name": "v1"}]
    r = data(d.query('{ q(func: uid(0x1)) { name } }'))
    assert r["q"] == [{"name": "v2"}]
    d.discard(t)


def test_blank_nodes_and_json_mutation():
    d = GraphDB(prefer_device=False)
    res = d.mutate(set_json={"name": "Zed", "pals": [{"name": "Yan"}]})
    assert len(res["uids"]) == 2
    r = data(d.query('{ q(func: has(pals)) { name pals { name } } }'))
    assert r["q"][0]["name"] == "Zed"
    assert r["q"][0]["pals"][0]["name"] == "Yan"


def test_facets(db):
    d = GraphDB(prefer_device=False)
    d.alter("friend: [uid] .")
    d.mutate(set_nquads='<0x1> <friend> <0x2> (close=true, since=2004) .')
    r = data(d.query('{ q(func: uid(0x1)) { friend @facets(close) { uid } } }'))
    fr = r["q"][0]["friend"][0]
    assert fr["friend|close"] is True


def test_wal_replay(tmp_path):
    path = str(tmp_path / "wal")
    d = GraphDB(wal_path=path, prefer_device=False)
    d.alter("name: string @index(exact) .")
    d.mutate(set_nquads='<0x1> <name> "Persisted" .')
    d.wal.close()
    d2 = GraphDB(wal_path=path, prefer_device=False)
    r = data(d2.query('{ q(func: eq(name, "Persisted")) { uid name } }'))
    assert r["q"] == [{"uid": "0x1", "name": "Persisted"}]


def test_wal_replay_overwrite_index(tmp_path):
    """Regression: replay must preserve the old-token index deletes of
    single-value overwrites (ops are logged expanded)."""
    path = str(tmp_path / "wal")
    d = GraphDB(wal_path=path, prefer_device=False)
    d.alter("name: string @index(exact) .")
    d.mutate(set_nquads='<0x1> <name> "Old" .')
    d.mutate(set_nquads='<0x1> <name> "New" .')
    d.wal.close()
    d2 = GraphDB(wal_path=path, prefer_device=False)
    assert data(d2.query('{ q(func: eq(name, "Old")) { uid } }'))["q"] == []
    assert data(d2.query('{ q(func: eq(name, "New")) { uid } }'))["q"] == \
        [{"uid": "0x1"}]


def test_wal_replay_implicit_schema(tmp_path):
    """Regression: predicates created on the fly (no alter) must replay
    with their inferred schema, not as DEFAULT scalars."""
    path = str(tmp_path / "wal")
    d = GraphDB(wal_path=path, prefer_device=False)
    d.mutate(set_json={"name": "Zed", "pals": [{"name": "Yan"}]})
    d.wal.close()
    d2 = GraphDB(wal_path=path, prefer_device=False)
    r = data(d2.query('{ q(func: has(pals)) { name pals { name } } }'))
    assert r["q"][0]["pals"][0]["name"] == "Yan"


def test_double_set_in_one_txn_clears_intermediate_index():
    """Regression: set name=v1 then name=v2 in ONE mutation must not
    leave a live index entry for v1."""
    d = GraphDB(prefer_device=False)
    d.alter("name: string @index(exact) .")
    d.mutate(set_nquads='<0x1> <name> "v1" .\n<0x1> <name> "v2" .')
    assert data(d.query('{ q(func: eq(name, "v1")) { uid } }'))["q"] == []
    assert data(d.query('{ q(func: eq(name, "v2")) { uid } }'))["q"] == \
        [{"uid": "0x1"}]
    d.rollup_all()
    assert data(d.query('{ q(func: eq(name, "v1")) { uid } }'))["q"] == []


def test_reverse_without_schema_errors():
    import pytest as _pytest
    from dgraph_tpu.gql import GQLError
    d = GraphDB(prefer_device=False)
    d.alter("friend: [uid] .")
    d.mutate(set_nquads='<0x1> <friend> <0x2> .')
    with _pytest.raises(GQLError, match="reverse"):
        d.query('{ q(func: uid(0x2)) { ~friend { uid } } }')
    with _pytest.raises(GQLError, match="reverse"):
        d.query('{ q(func: uid(0x2)) @recurse(depth: 2) { ~friend } }')


def test_count_uid_sums(db):
    r = data(db.query("{ q(func: has(friend)) { count(uid) } }"))
    assert r["q"] == [{"count": 3}]
    r = data(db.query('{ q(func: eq(name, "Michonne")) { friend { count(uid) } } }'))
    assert r["q"][0]["friend"] == [{"count": 4}]


def test_eq_own_value_var():
    """eq(pred, val(v)) compares each uid against ITS OWN value."""
    d = GraphDB(prefer_device=False)
    d.alter("age: int @index(int) .\ntarget: int .")
    d.mutate(set_nquads="""
      <0x1> <age> "10" .
      <0x1> <target> "20" .
      <0x2> <age> "20" .
      <0x2> <target> "20" .
    """)
    r = data(d.query('''{
      var(func: has(target)) { t as target }
      q(func: has(age)) @filter(eq(age, val(t))) { uid }
    }'''))
    assert r["q"] == [{"uid": "0x2"}]


def test_facets_not_attached_to_prior_sibling():
    """Regression: facets of a cascade-dropped child must not land on
    the previously emitted sibling."""
    d = GraphDB(prefer_device=False)
    d.alter("friend: [uid] .\nname: string .")
    d.mutate(set_nquads="""
      <0x1> <friend> <0x2> (weight=1) .
      <0x1> <friend> <0x3> (weight=99) .
      <0x2> <name> "has-name" .
    """)
    r = data(d.query('''{
      q(func: uid(0x1)) { friend @facets(weight) @cascade { name } }
    }'''))
    fr = r["q"][0]["friend"]
    assert len(fr) == 1
    assert fr[0]["friend|weight"] == 1


def test_count_between_filter():
    # review regression: between(count(p), lo, hi) must work (it
    # previously raised) — both at root and under a live overlay
    d = GraphDB(prefer_device=False)
    d.alter("f: [uid] @count .")  # root count comparisons need @count
    lines = []
    for s in range(1, 8):
        for k in range(s):  # uid s has s edges
            lines.append(f"<{s:#x}> <f> <{0x50 + k:#x}> .")
    d.mutate(set_nquads="\n".join(lines))
    out = d.query("{ q(func: between(count(f), 3, 5)) { uid } }")
    assert [r["uid"] for r in out["data"]["q"]] == ["0x3", "0x4", "0x5"]
    d.rollup_all()
    d.rollup_in_read = False
    d.mutate(set_nquads="<0x2> <f> <0x90> .\n<0x2> <f> <0x91> .")
    out = d.query("{ q(func: between(count(f), 3, 5)) { uid } }")
    assert [r["uid"] for r in out["data"]["q"]] == \
        ["0x2", "0x3", "0x4", "0x5"]


def test_count_between_missing_tablet_zero_case():
    # review regression: between(count(missing), 0, N) matches every
    # candidate (their count is 0, inside the range)
    d = GraphDB(prefer_device=False)
    d.alter("name: string @index(exact) .")
    d.mutate(set_nquads='<1> <name> "a" .\n<2> <name> "b" .')
    out = d.query('{ q(func: has(name)) '
                  '@filter(between(count(nope), 0, 5)) { uid } }')
    assert [r["uid"] for r in out["data"]["q"]] == ["0x1", "0x2"]
    out = d.query('{ q(func: has(name)) '
                  '@filter(between(count(nope), 1, 5)) { uid } }')
    assert out["data"]["q"] == []


def test_count_zero_case_all_ops():
    d = GraphDB(prefer_device=False)
    d.alter("name: string @index(exact) .")
    d.mutate(set_nquads='<1> <name> "a" .')
    def q(flt):
        out = d.query('{ q(func: has(name)) @filter(%s) { uid } }' % flt)
        return [r["uid"] for r in out["data"]["q"]]
    assert q("ge(count(nope), 0)") == ["0x1"]
    assert q("le(count(nope), 0)") == ["0x1"]
    assert q("gt(count(nope), 0)") == []
    assert q("eq(count(nope), 0)") == ["0x1"]


def test_applied_commit_record_feeds_conflict_window():
    """Review regression: a commit record applied through the Raft
    path (apply_record) must land in the local oracle's conflict
    window, so a replica that later becomes leader aborts open txns
    that raced the replicated write (ref posting/oracle.go:207
    ProcessDelta mirroring Zero's commit decisions)."""
    import pytest

    from dgraph_tpu.cluster.coordinator import TxnAborted
    from dgraph_tpu.engine.db import GraphDB

    db1, db2 = GraphDB(), GraphDB()
    recs = []
    db2.on_record = recs.append
    for db in (db1, db2):
        db.alter("bal: int .")
    db2.mutate(set_nquads='<0x1> <bal> "100" .')
    for r in recs:
        db1.fast_forward_ts(db1.apply_record(r))
    recs.clear()

    # open a local txn touching (bal, 0x1), then apply a FOREIGN
    # commit record for the same key with a later commit_ts (what a
    # follower sees when another leader's write replicates in)
    txn = db1.new_txn()
    db1.mutate(txn, commit_now=False, set_nquads='<0x1> <bal> "50" .')
    db2.mutate(set_nquads='<0x1> <bal> "70" .')
    kind, _cts, staged, schemas = recs[0]
    foreign = (kind, txn.start_ts + 5, staged, schemas)
    db1.fast_forward_ts(db1.apply_record(foreign))

    with pytest.raises(TxnAborted):
        db1.commit(txn)
    # the racing write won (no lost update)
    out = db1.query('{ q(func: uid(0x1)) { bal } }')
    assert out["data"]["q"] == [{"bal": 70}]


def test_lang_eq_selects_the_addressed_posting():
    """Ref query0_test.go TestQueryEmptyDefaultNames /
    NamesThatAreEmptyInLanguage: eq(name, v) addresses ONLY the
    untagged posting, eq(name@hi, v) only the @hi posting — lang
    variants share index buckets, so hits must verify against the
    selected posting."""
    db2 = GraphDB(prefer_device=False)
    db2.alter("name: string @index(exact) @lang .")
    db2.mutate(set_nquads="\n".join([
        '<0x1> <name> "" .',
        '<0x2> <name> "" .', '<0x2> <name> "Amit"@en .',
        '<0x2> <name> "अमित"@hi .',
        '<0x3> <name> "Andrew"@en .', '<0x3> <name> ""@hi .']))
    r = data(db2.query('{ q(func: eq(name, "")) { uid } }'))
    assert [x["uid"] for x in r["q"]] == ["0x1", "0x2"]
    r = data(db2.query('{ q(func: eq(name@hi, "")) { name@en } }'))
    assert r["q"] == [{"name@en": "Andrew"}]
    r = data(db2.query('{ q(func: eq(name@hi, "अमित")) { name@en } }'))
    assert r["q"] == [{"name@en": "Amit"}]


def test_lang_star_expands_all_languages():
    """name@* emits every language as its own key plus the untagged
    value (ref query0_test.go TestQueryAllLanguages)."""
    db2 = GraphDB(prefer_device=False)
    db2.alter("name: string @index(exact) @lang .")
    db2.mutate(set_nquads="\n".join([
        '<0x2> <name> "" .', '<0x2> <name> "Amit"@en .',
        '<0x2> <name> "अमित"@hi .']))
    r = data(db2.query('{ q(func: uid(0x2)) { name@* } }'))
    assert r["q"] == [{"name": "", "name@en": "Amit",
                       "name@hi": "अमित"}]


def test_facet_var_sibling_aggregation():
    """Level-based facet var consumed by a sibling aggregation in the
    SAME block, attached inside the parent row (ref query0_test.go
    TestLevelBasedFacetVarAggSum)."""
    db2 = GraphDB(prefer_device=False)
    db2.alter("path: [uid] .\nname: string .")
    db2.mutate(set_nquads="\n".join([
        '<0x10> <path> <0x11> (weight=0.1) .',
        '<0x10> <path> <0x12> (weight=0.7) .',
        '<0x11> <name> "John" .', '<0x12> <name> "Matt" .']))
    r = data(db2.query('''{
      friend(func: uid(0x10)) {
        path @facets(L1 as weight)
        sumw: sum(val(L1))
      }
    }'''))
    assert len(r["friend"]) == 1
    row = r["friend"][0]
    assert abs(row["sumw"] - 0.8) < 1e-9
    assert len(row["path"]) == 2


def test_count_reverse_filter():
    """count(~pred) counts incoming edges in root funcs and filters
    (ref query2_test.go TestCountReverseFunc)."""
    db2 = GraphDB(prefer_device=False)
    db2.alter("name: string @index(exact) .\nfriend: [uid] @reverse @count .")
    db2.mutate(set_nquads="\n".join([
        '<0x1> <name> "M" .', '<0x17> <name> "Rick" .',
        '<0x18> <name> "Glenn" .',
        "<0x1> <friend> <0x17> .", "<0x1> <friend> <0x18> .",
        "<0x18> <friend> <0x1> .",
    ]))
    r = data(db2.query('{ q(func: ge(count(~friend), 1)) { name } }'))
    assert sorted(x["name"] for x in r["q"]) == ["Glenn", "M", "Rick"]
    r = data(db2.query(
        '{ q(func: has(name)) @filter(ge(count(~friend), 2)) { name } }'))
    assert r["q"] == []
    r = data(db2.query(
        '{ q(func: eq(count(~friend), 1)) { name } }'))
    assert sorted(x["name"] for x in r["q"]) == ["Glenn", "M", "Rick"]


def test_graphdb_options_are_the_ones_callers_set():
    """GraphDB's keyword set, pinned: an option comes (or comes back)
    with the caller that sets it, and this list changes with it."""
    import inspect

    assert sorted(inspect.signature(GraphDB.__init__).parameters) == [
        "device_hbm_budget", "device_min_edges", "enc_key",
        "fused_min_rows", "mesh", "plan_cache_size", "planner",
        "planner_explore", "prefer_columnar", "prefer_compressed",
        "prefer_device", "prefer_fused", "prefetch_workers",
        "result_cache_entries", "rollup_window", "self",
        "shard_min_edges", "store_dir", "tablet_budget",
        "vec_index_min_rows", "vec_quantized", "wal_path"]
